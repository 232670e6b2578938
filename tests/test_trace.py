"""The program's span and counter recorder (repro.core.trace) and the
named scopes of the engine's device programs."""
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core import trace
from repro.core.buffer import BufferEntry, Mode, StatefulRolloutBuffer
from repro.data import logic
from repro.models.model import build_model
from repro.rollout.engine import SlotEngine
from repro.train.loop import tiny_lm_config

STEP_PARTS = ["engine.step.prepare", "engine.step.launch",
              "engine.step.wait", "engine.step.events"]


@pytest.fixture
def recorder():
    rec = trace.enable()
    yield rec
    trace.disable()


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_lm_config(len(logic.VOCAB), d_model=64, layers=2, heads=2)
    m = build_model(cfg)
    return m, m.init_params(jax.random.PRNGKey(0))


def _engine(tiny, **kw):
    m, params = tiny
    return SlotEngine(m, lambda: params, capacity=4, max_total_len=64,
                      max_gen_len=8, eos_id=-1, pad_id=logic.VOCAB.pad_id,
                      **kw)


def _children(spans, i):
    return [s for s in spans if s.parent == i]


def test_off_span_is_one_null_object_and_reads_no_clock(monkeypatch):
    trace.disable()

    def no_clock():
        raise AssertionError("the clock was read with the recorder off")
    monkeypatch.setattr(time, "perf_counter_ns", no_clock)
    a, b = trace.span("engine.step"), trace.span("sched.fill")
    assert a is b
    with a:
        with b:
            pass
    trace.stamp(3, "done")
    monkeypatch.undo()
    rec = trace.enable()
    try:
        got = rec.take()
    finally:
        trace.disable()
    assert got.spans == [] and got.stamps == {} and got.compiles == {}


def test_take_without_a_recorder_raises():
    trace.disable()
    with pytest.raises(RuntimeError):
        trace.take()


def test_nesting_parent_self_time_and_take(recorder):
    with trace.span("a"):
        with trace.span("b"):
            time.sleep(0.002)
        with trace.span("c"):
            with trace.span("d"):
                pass
    with trace.span("a"):
        pass
    got = trace.take()
    assert [s.name for s in got.spans] == ["a", "b", "c", "d", "a"]
    assert [s.parent for s in got.spans] == [-1, 0, 0, 2, -1]
    for s in got.spans:
        assert s.end_ns >= s.start_ns
        if s.parent >= 0:
            p = got.spans[s.parent]
            assert p.start_ns <= s.start_ns and s.end_ns <= p.end_ns
    own = trace.self_ns(got.spans)
    a, b, c, d = got.spans[:4]
    assert own[0] == (a.end_ns - a.start_ns) - (b.end_ns - b.start_ns) \
        - (c.end_ns - c.start_ns)
    assert own[2] == (c.end_ns - c.start_ns) - (d.end_ns - d.start_ns)
    assert own[1] >= 2_000_000 and own[0] >= 0
    # the cumulative by-name view outlives take(); the spans do not
    assert recorder.count == {"a": 2, "b": 1, "c": 1, "d": 1}
    assert recorder.total["b"] >= 0.002
    assert trace.take().spans == []


def test_take_inside_an_open_span_raises(recorder):
    with trace.span("a"):
        with pytest.raises(RuntimeError):
            trace.take()


def test_engine_step_splits_into_four_consecutive_parts(tiny, recorder):
    eng = _engine(tiny)
    # two entries share a prompt: the first step copies a page on write
    prompts = [[1, 5, 6, 7], [1, 5, 6, 7], [1, 9, 8, 7, 6]]
    eng.submit([BufferEntry(uid=i, prompt=p) for i, p in enumerate(prompts)],
               0)
    for _ in range(3):
        eng.step()
    eng.interrupt([0])
    spans = trace.take().spans
    names = [s.name for s in spans]
    assert names.count("engine.step") == 3
    assert names.count("engine.interrupt") == 1
    sub = names.index("engine.submit")
    assert {s.name for s in _children(spans, sub)} == {"engine.prefill",
                                                       "engine.page_scatter"}
    for i, s in enumerate(spans):
        if s.name != "engine.step":
            continue
        parts = _children(spans, i)
        assert [p.name for p in parts] == STEP_PARTS
        assert parts[0].start_ns >= s.start_ns
        assert parts[-1].end_ns <= s.end_ns
        for x, y in zip(parts, parts[1:]):
            assert x.end_ns <= y.start_ns
        covered = sum(p.end_ns - p.start_ns for p in parts)
        assert covered >= 0.95 * (s.end_ns - s.start_ns)


def test_stamps_through_harvest_and_resume_give_done_to_consume():
    buf = StatefulRolloutBuffer(Mode.PARTIAL)
    uids = buf.load_prompts([[1, 2], [1, 3]])
    trace.stamp(uids[0], "running")          # off: nothing recorded
    trace.enable()
    try:
        buf.mark_running(uids)
        buf.record_tokens(uids[0], [5], [-1.0], 0)
        buf.scavenge(uids[0])                # harvested ...
        buf.mark_running([uids[0]])          # ... and resumed
        buf.record_tokens(uids[0], [6], [-1.0], 0)
        buf.mark_done(uids[0], "length")
        buf.mark_done(uids[1], "length")
        time.sleep(0.01)
        buf.consume([uids[0]])
        stamps = trace.take().stamps
    finally:
        trace.disable()
    s0 = stamps[uids[0]]
    assert list(s0) == ["running", "harvest", "done", "consume"]
    assert s0["running"] <= s0["harvest"] <= s0["done"] <= s0["consume"]
    waits = trace.between(stamps, "done", "consume")
    assert set(waits) == {uids[0]}          # uid 1 is not consumed yet
    assert waits[uids[0]] == (s0["consume"] - s0["done"]) * 1e-9
    assert waits[uids[0]] >= 0.01


def test_compile_is_counted_under_the_open_span(recorder):
    fresh = jax.jit(lambda x: x * 3.0 + 0.25)
    with trace.span("outer"):
        with trace.span("inner"):
            fresh(jnp.arange(7.0)).block_until_ready()
        fresh(jnp.arange(7.0)).block_until_ready()   # compiled already
    got = trace.take().compiles
    assert got["inner"][0] >= 1 and got["inner"][1] > 0
    assert "outer" not in got


def test_spans_reach_a_running_profiler_trace(tiny, tmp_path):
    """With the recorder off, spans are written into a jax.profiler trace
    while one runs, and are null again once it stops."""
    trace.disable()
    eng = _engine(tiny)
    eng.submit([BufferEntry(uid=0, prompt=[1, 5, 6, 7])], 0)
    eng.step()                               # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace.span("engine.step") is not trace.span("engine.step")
        eng.step()
    finally:
        jax.profiler.stop_trace()
    assert trace.span("x") is trace.span("y")
    path = next(tmp_path.rglob("*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(path))
    names = {ev.name for plane in pd.planes if plane.name.startswith("/host")
             for line in plane.lines for ev in line.events}
    assert {"engine.step", *STEP_PARTS} <= names


def test_decode_program_carries_its_scope_names(tiny):
    eng = _engine(tiny)
    B = eng.capacity
    lowered = jax.jit(eng._paged_decode_fn).lower(
        tiny[1], jnp.zeros(B, jnp.int32), eng.cache, eng.kv_scales,
        jnp.zeros((B, 2), jnp.int32), jnp.zeros(B, jnp.int32),
        jax.random.PRNGKey(0))
    hlo = lowered.compile().as_text()
    for scope in ("decode/model", "/attend/", "decode/sample",
                  "decode/write_kv"):
        assert scope in hlo, scope


def test_prefill_program_carries_its_scope_name(tiny):
    eng = _engine(tiny)
    eng.submit([BufferEntry(uid=0, prompt=[1, 5, 6, 7])], 0)
    (fn,) = eng._prefill_cache.values()
    assert fn.__name__ == "prefill"          # the program keeps its name
    toks = jnp.zeros((1, 4), jnp.int32)
    lowered = fn.lower(tiny[1], {"tokens": toks,
                                 "prompt_lens": jnp.asarray([3])},
                       eng.model.init_cache(1, 16))
    assert "prefill/" in lowered.compile().as_text()
