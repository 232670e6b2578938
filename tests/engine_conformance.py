"""Engine-conformance suite: the executable contract of EngineProtocol.

Every scenario runs IDENTICALLY against the discrete-event SimEngine and a
tiny-model SlotEngine (real JAX decode), so any future engine backend can
be added to ``ENGINES`` and inherit the whole contract:

  * free-slot accounting — submit/step/interrupt move slots between free
    and active exactly; capacity is never exceeded
  * event/uid consistency — step() emits exactly one event per active
    slot, in a stable order, for exactly the active uids
  * finish reasons — done events carry "eos" | "length"; non-done events
    carry None; done uids leave their slots immediately
  * interrupt idempotence — a second interrupt is a no-op returning []
  * scavenge/resume (both buffer modes) and oversubscription refill
    compose with StatefulRolloutBuffer without violating its invariants

Also pins down the SlotEngine hot-path guarantees of this PR: a loop-free
``step()`` and a bucketed (bounded) ``_prefill_cache``.
"""
import ast
import inspect
import math
import textwrap

import pytest

from repro.core.buffer import BufferEntry, Mode, StatefulRolloutBuffer
from repro.core.engine_api import EngineProtocol, SlotTable
from repro.rollout.sim import SimEngine

CAPACITY = 4
MAX_GEN = 6
MAX_TOTAL = 64

_TINY = {}


def _tiny_model():
    if not _TINY:
        import jax
        from repro.data import logic
        from repro.models.model import build_model
        from repro.train.loop import tiny_lm_config
        cfg = tiny_lm_config(len(logic.VOCAB), d_model=32, layers=1, heads=2)
        model = build_model(cfg)
        _TINY["model"] = model
        _TINY["params"] = model.init_params(jax.random.PRNGKey(0))
        _TINY["pad"] = logic.VOCAB.pad_id
    return _TINY


def make_sim(capacity=CAPACITY, max_gen=MAX_GEN):
    return SimEngine(capacity=capacity, max_gen_len=max_gen, seed=0)


def make_slot(capacity=CAPACITY, max_gen=MAX_GEN, eos_id=-1, **kw):
    from repro.rollout.engine import SlotEngine
    t = _tiny_model()
    # eos_id=-1: finishes are budget-driven, so scenarios are deterministic
    return SlotEngine(t["model"], lambda: t["params"], capacity=capacity,
                      max_total_len=MAX_TOTAL, max_gen_len=max_gen,
                      eos_id=eos_id, pad_id=t["pad"], temperature=1.0, **kw)


def _tiny_left_model():
    """Smallest left-padding (ssm) model — exercises the kv_start/width
    accounting path the transformer engine never touches."""
    if "left_model" not in _TINY:
        import jax
        import jax.numpy as jnp
        from repro.configs.base import get_smoke_config
        from repro.models.model import build_model
        cfg = get_smoke_config("xlstm_125m").replace(
            param_dtype=jnp.float32, compute_dtype=jnp.float32)
        model = build_model(cfg)
        assert model.padding_side == "left"
        _TINY["left_model"] = model
        _TINY["left_params"] = model.init_params(jax.random.PRNGKey(1))
    return _TINY["left_model"], _TINY["left_params"]


def make_slot_left(capacity=CAPACITY, max_gen=MAX_GEN, eos_id=-1,
                   max_total=MAX_TOTAL):
    from repro.rollout.engine import SlotEngine
    model, params = _tiny_left_model()
    return SlotEngine(model, lambda: params, capacity=capacity,
                      max_total_len=max_total, max_gen_len=max_gen,
                      eos_id=eos_id, pad_id=0, temperature=1.0)


def make_slot_dense(capacity=CAPACITY, max_gen=MAX_GEN, eos_id=-1):
    """Dense-cache SlotEngine (the pre-paging memory model, kept as an
    escape hatch for exotic cache layouts and as the oracle for the paged
    engine's token stream)."""
    from repro.rollout.engine import SlotEngine
    t = _tiny_model()
    return SlotEngine(t["model"], lambda: t["params"], capacity=capacity,
                      max_total_len=MAX_TOTAL, max_gen_len=max_gen,
                      eos_id=eos_id, pad_id=t["pad"], temperature=1.0,
                      paged=False)


def make_group_sim(capacity=CAPACITY, max_gen=MAX_GEN, n_replicas=2):
    """EngineGroup over SimEngine replicas (distinct seeds, shared total
    capacity) — the multi-replica facade must satisfy the whole contract."""
    from repro.rollout.group import EngineGroup
    assert capacity % n_replicas == 0
    return EngineGroup([SimEngine(capacity=capacity // n_replicas,
                                  max_gen_len=max_gen, seed=i)
                        for i in range(n_replicas)])


def make_group_slot(capacity=CAPACITY, max_gen=MAX_GEN, eos_id=-1,
                    n_replicas=2, **kw):
    """EngineGroup over paged SlotEngine replicas, each with its own
    page pool."""
    from repro.rollout.group import EngineGroup
    assert capacity % n_replicas == 0
    return EngineGroup([make_slot(capacity=capacity // n_replicas,
                                  max_gen=max_gen, eos_id=eos_id, **kw)
                        for _ in range(n_replicas)])


def make_group_mig(capacity=CAPACITY, max_gen=MAX_GEN, eos_id=-1,
                   n_replicas=2, **kw):
    """EngineGroup with cross-replica KV migration enabled: stolen
    entries carry their resident pages to the thief's pool instead of
    re-prefilling.  The whole single-engine contract must still hold."""
    from repro.rollout.group import EngineGroup
    assert capacity % n_replicas == 0
    return EngineGroup([make_slot(capacity=capacity // n_replicas,
                                  max_gen=max_gen, eos_id=eos_id, **kw)
                        for _ in range(n_replicas)], migrate_kv=True)


def make_slot_packed(capacity=CAPACITY, max_gen=MAX_GEN, eos_id=-1, **kw):
    """Packed ragged prefill: one segment-masked launch per fill wave."""
    return make_slot(capacity=capacity, max_gen=max_gen, eos_id=eos_id,
                     packed_prefill=True, **kw)


def make_slot_fused(capacity=CAPACITY, max_gen=MAX_GEN, eos_id=-1, **kw):
    """Fused greedy sampling (streaming LM head, no (B, V) round-trip).
    The flag only changes the decode compile at temperature 0; the
    contract must hold for sampled decode too."""
    return make_slot(capacity=capacity, max_gen=max_gen, eos_id=eos_id,
                     fused_sampling=True, **kw)


def make_slot_int8(capacity=CAPACITY, max_gen=MAX_GEN, eos_id=-1, **kw):
    """int8 KV pages with per-page scale planes."""
    return make_slot(capacity=capacity, max_gen=max_gen, eos_id=eos_id,
                     kv_quant="int8", **kw)


ENGINES = [("sim", make_sim), ("slot", make_slot),
           ("slot_dense", make_slot_dense), ("slot_left", make_slot_left),
           ("slot_packed", make_slot_packed), ("slot_fused", make_slot_fused),
           ("slot_int8", make_slot_int8),
           ("group_sim", make_group_sim), ("group_slot", make_group_slot),
           ("group_mig", make_group_mig)]


@pytest.fixture(params=[name for name, _ in ENGINES])
def engine_factory(request):
    return dict(ENGINES)[request.param]


def entries(n, start_uid=0, prompt_len=3):
    return [BufferEntry(uid=start_uid + i, prompt=[1] * prompt_len + [2 + i])
            for i in range(n)]


def checked_step(engine):
    """One engine step with the full event contract asserted."""
    before = sorted(engine.active_uids())
    free_before = engine.free_slots()
    evs = engine.step()
    assert sorted(ev.uid for ev in evs) == before, \
        "one event per active slot, for exactly the active uids"
    done_uids = {ev.uid for ev in evs if ev.done}
    assert set(engine.active_uids()) == set(before) - done_uids, \
        "done slots freed, others retained"
    assert engine.free_slots() == free_before + len(done_uids)
    for ev in evs:
        assert isinstance(ev.token, int)
        assert math.isfinite(ev.logprob)
        assert (ev.finish_reason is None) == (not ev.done)
        if ev.done:
            assert ev.finish_reason in ("eos", "length")
    return evs


def run_to_completion(engine, max_steps=10_000):
    all_events = []
    steps = 0
    while engine.active_uids():
        all_events.extend(checked_step(engine))
        steps += 1
        assert steps < max_steps, "engine failed to drain"
    return all_events


# -- scenarios ----------------------------------------------------------------

def test_protocol_surface(engine_factory):
    eng = engine_factory()
    assert isinstance(eng, EngineProtocol)
    assert eng.capacity == CAPACITY
    assert isinstance(eng.clock, float)
    assert eng.free_slots() == CAPACITY and eng.active_uids() == []
    eng.sync_weights(3)
    assert eng.version == 3


def test_submit_accounting(engine_factory):
    eng = engine_factory()
    es = entries(3)
    eng.submit(es, version=0)
    assert eng.free_slots() == CAPACITY - 3
    assert sorted(eng.active_uids()) == [0, 1, 2]
    # overfilling the remaining slot must raise
    with pytest.raises(AssertionError):
        eng.submit(entries(2, start_uid=10), version=0)
    eng.submit(entries(1, start_uid=10), version=0)
    assert eng.free_slots() == 0


def test_step_events_and_budget(engine_factory):
    eng = engine_factory()
    eng.submit(entries(CAPACITY), version=0)
    evs = run_to_completion(eng)
    assert eng.free_slots() == CAPACITY
    per_uid = {u: sum(1 for e in evs if e.uid == u)
               for u in range(CAPACITY)}
    # generation budget is a per-trajectory cap
    assert all(1 <= n <= MAX_GEN for n in per_uid.values()), per_uid
    assert all(sum(1 for e in evs if e.uid == u and e.done) == 1
               for u in per_uid)


def test_event_order_stable_while_resident(engine_factory):
    """While a set of requests stays resident, the per-step event order
    does not change (ascending slot order contract)."""
    eng = engine_factory()
    eng.submit(entries(CAPACITY), version=0)
    order0 = [ev.uid for ev in checked_step(eng)]
    while True:
        uids_before = set(eng.active_uids())
        evs = checked_step(eng)
        assert [ev.uid for ev in evs] == [u for u in order0
                                          if u in uids_before]
        if not eng.active_uids():
            break


def test_interrupt_idempotent(engine_factory):
    eng = engine_factory()
    eng.submit(entries(3), version=0)
    checked_step(eng)
    survivors = sorted(eng.active_uids())
    out = eng.interrupt()
    assert sorted(out) == survivors
    assert eng.free_slots() == CAPACITY and eng.active_uids() == []
    assert eng.interrupt() == []              # idempotent on empty
    assert eng.interrupt(uids=[99]) == []     # unknown uid: no-op


def test_interrupt_selective(engine_factory):
    eng = engine_factory()
    eng.submit(entries(3), version=0)
    out = eng.interrupt(uids=[1])
    assert out == [1]
    assert sorted(eng.active_uids()) == [0, 2]
    assert eng.free_slots() == CAPACITY - 2
    # slots freed by interrupt are immediately reusable
    eng.submit(entries(2, start_uid=20), version=0)
    assert eng.free_slots() == 0


@pytest.mark.parametrize("mode", [Mode.ON_POLICY, Mode.PARTIAL])
def test_scavenge_resume_cycle(engine_factory, mode):
    """interrupt -> buffer.scavenge -> resubmit honours per-mode semantics
    and the engine treats the scavenged prefix as part of the budget."""
    eng = engine_factory()
    buf = StatefulRolloutBuffer(mode)
    uids = buf.load_prompts([[1, 2, 3]] * 2)
    buf.mark_running(uids)
    eng.submit(buf.running(), version=0)
    for _ in range(2):
        for ev in checked_step(eng):
            buf.record_tokens(ev.uid, [ev.token], [ev.logprob], 0)
            if ev.done:
                buf.mark_done(ev.uid, ev.finish_reason)
    for uid in eng.interrupt():
        buf.scavenge(uid)
    buf.check_invariants()
    for e in buf.pending():
        assert (e.gen_len == 0) if mode == Mode.ON_POLICY else True
    # resume: remaining budget shrinks by the scavenged prefix
    resumed = buf.pending()
    if resumed:
        buf.mark_running([e.uid for e in resumed])
        prefixes = {e.uid: e.gen_len for e in resumed}
        eng.submit(resumed, version=1)
        evs = [ev for ev in run_to_completion(eng)]
        for ev in evs:
            buf.record_tokens(ev.uid, [ev.token], [ev.logprob], 1)
            if ev.done:
                buf.mark_done(ev.uid, ev.finish_reason)
        for uid, prefix in prefixes.items():
            new = sum(1 for ev in evs if ev.uid == uid)
            assert prefix + new <= MAX_GEN
    buf.check_invariants()
    for e in buf.done():
        assert len(e.generated) == len(e.logprobs) == len(e.versions)


def test_oversubscription_refill(engine_factory):
    """More prompts than slots: refilling freed slots every step drains the
    whole workload with slot accounting intact throughout."""
    n = 3 * CAPACITY
    eng = engine_factory()
    buf = StatefulRolloutBuffer(Mode.ON_POLICY)
    buf.load_prompts([[1, 1 + i % 5] for i in range(n)])
    steps = 0
    while buf.pending() or buf.running():
        batch = buf.pending()[:eng.free_slots()]
        if batch:
            buf.mark_running([e.uid for e in batch])
            eng.submit(batch, version=0)
        assert len(eng.active_uids()) == len(buf.running())
        assert eng.free_slots() == CAPACITY - len(buf.running())
        for ev in checked_step(eng):
            buf.record_tokens(ev.uid, [ev.token], [ev.logprob], 0)
            if ev.done:
                buf.mark_done(ev.uid, ev.finish_reason)
        steps += 1
        assert steps < 10_000
    assert len(buf.done()) == n
    assert eng.free_slots() == CAPACITY
    buf.check_invariants()


def test_step_on_empty_engine(engine_factory):
    eng = engine_factory()
    assert eng.step() == []
    assert eng.free_slots() == CAPACITY


# -- SlotEngine hot-path guarantees (this PR's tentpole) ----------------------

def test_slot_engine_step_is_loop_free():
    """step() must stay vectorized: no per-slot Python for/while loop
    (comprehensions build the event list; state updates are array ops)."""
    from repro.rollout.engine import SlotEngine
    tree = ast.parse(textwrap.dedent(inspect.getsource(SlotEngine.step)))
    loops = [n for n in ast.walk(tree)
             if isinstance(n, (ast.For, ast.While, ast.AsyncFor))]
    assert not loops, "per-slot Python loop reintroduced in SlotEngine.step"


def test_prefill_cache_bounded_by_bucketing():
    """Submitting many distinct (width, batch) shapes compiles at most
    O(log max_total_len * log capacity) prefill variants, keyed by
    power-of-two buckets."""
    eng = make_slot(capacity=CAPACITY)
    uid = 0
    shapes = [(1, 1), (2, 1), (3, 2), (5, 3), (6, 4), (9, 2), (11, 1),
              (13, 3), (17, 4), (21, 2), (26, 1), (30, 4)]
    for plen, k in shapes:
        es = [BufferEntry(uid=uid + i, prompt=[1] * (plen + 1))
              for i in range(k)]
        uid += k
        eng.submit(es, version=0)
        eng.interrupt()
    n_width_buckets = int(math.log2(MAX_TOTAL)) + 1
    n_batch_buckets = int(math.ceil(math.log2(CAPACITY))) + 1
    assert len(eng._prefill_cache) <= n_width_buckets * n_batch_buckets
    # far fewer compiles than distinct submitted shapes
    assert len(eng._prefill_cache) < len(shapes)
    for width, kb, dtype_key in eng._prefill_cache:
        assert width == 1 << (width - 1).bit_length() or width == MAX_TOTAL
        assert kb == 1 << (kb - 1).bit_length() or kb == CAPACITY
        assert dtype_key == eng._kv_dtype_key


def test_prefill_and_decode_caches_keyed_by_kv_dtype():
    """Regression: an int8 engine and an fp engine with the same (width,
    batch) bucket must NOT share compiled prefill/decode entries — the KV
    dtype is part of every compile-cache key, so a shared cache dict (or
    a future engine pooling compiles across replicas) cannot alias an
    int8 page layout onto an fp one."""
    fp = make_slot(capacity=CAPACITY)
    q = make_slot(capacity=CAPACITY, kv_quant="int8")
    assert fp._kv_dtype_key != q._kv_dtype_key
    for uid, eng in ((0, fp), (100, q)):
        eng.submit([BufferEntry(uid=uid, prompt=[1, 2, 3])], version=0)
        eng.step()
    assert not set(fp._prefill_cache) & set(q._prefill_cache)
    assert not set(fp._paged_decode_cache) & set(q._paged_decode_cache)
    # fused-vs-unfused decode variants are distinct compiles too
    fz = make_slot(capacity=CAPACITY, fused_sampling=True)
    fz.temperature = 0.0
    fz.submit([BufferEntry(uid=7, prompt=[1, 2, 3])], version=0)
    fz.step()
    assert not set(fp._paged_decode_cache) & set(fz._paged_decode_cache)


def test_left_padding_bucketing_keeps_gen_headroom():
    """Width bucketing must not eat a left-padding model's generation
    budget: a prompt wider than max_total_len/2 would bucket to
    max_total_len, set kv_len there, and terminate after one token."""
    max_gen = 8
    eng = make_slot_left(capacity=1, max_gen=max_gen, max_total=MAX_TOTAL)
    plen = MAX_TOTAL // 2 + 4                     # pow2-buckets to MAX_TOTAL
    eng.submit([BufferEntry(uid=0, prompt=[1] * plen)], version=0)
    assert int(eng.slots.kv_len[0]) + max_gen < MAX_TOTAL, \
        "bucketed width left no room for the generation budget"
    evs = run_to_completion(eng)
    assert len(evs) == max_gen                    # full budget generated
    assert evs[-1].done and evs[-1].finish_reason == "length"


def test_slot_table_shared_by_both_engines():
    """Both engines expose the same SlotTable host state — the struct any
    new backend should reuse."""
    from repro.rollout.engine import SlotEngine   # noqa: F401
    for _, factory in ENGINES:
        eng = factory()
        assert isinstance(eng.slots, SlotTable)
        assert eng.slots.capacity == eng.capacity


# -- paged KV cache accounting (PR 3 tentpole) --------------------------------
#
# The default SlotEngine above already runs every scenario on the paged
# memory model; these cases additionally pin down the page-pool contract:
# prefix sharing, copy-on-write isolation, resume-without-reprefill, and
# zero leaked pages/references at quiescence.

def _drained_pool_is_clean(eng):
    assert not eng.active_uids()
    st = eng.cache_stats()
    assert st["pages_in_use"] == 0, st
    assert (eng.kv.pool.refcount == 0).all()
    eng.kv.check_invariants()


def group_entries(g, prompt_len=8, start_uid=0):
    """A GRPO-style group: identical prompt, one entry per member."""
    return [BufferEntry(uid=start_uid + i, prompt=[1] * prompt_len)
            for i in range(g)]


def test_paged_group_prefills_shared_prompt_once():
    """G same-prompt members cost ONE prefill of the shared prefix; the
    other G-1 map the same pages, and every reference drops to zero when
    the group finishes (no leaked pages)."""
    g, plen = 4, 8
    eng = make_slot()
    eng.submit(group_entries(g, plen), version=0)
    st = eng.cache_stats()
    assert st["prefill_tokens_run"] == plen - 1
    assert st["prefill_tokens_saved"] == (g - 1) * (plen - 1)
    assert st["shared_prefills"] == g - 1
    run_to_completion(eng)
    _drained_pool_is_clean(eng)


def test_paged_cow_keeps_group_members_isolated():
    """Members sharing a partial prefix page diverge via copy-on-write;
    the paged token streams match the dense engine's exactly (greedy)."""
    def run(factory):
        eng = factory()
        es = [BufferEntry(uid=i, prompt=[1, 2, 3, 4, 2 + i])
              for i in range(3)] + [BufferEntry(uid=9, prompt=[3, 1, 4])]
        eng.submit(es, version=0)
        toks = {e.uid: [] for e in es}
        while eng.active_uids():
            for ev in checked_step(eng):
                toks[ev.uid].append(ev.token)
        return toks

    def greedy_paged():
        eng = make_slot()
        eng.temperature = 0.0
        return eng

    def greedy_dense():
        eng = make_slot_dense()
        eng.temperature = 0.0
        return eng

    paged, dense = run(greedy_paged), run(greedy_dense)
    assert paged == dense, (paged, dense)


def _greedy_stream(factory, prompts, max_gen=MAX_GEN):
    eng = factory(capacity=8, max_gen=max_gen)
    eng.temperature = 0.0
    es = [BufferEntry(uid=i, prompt=list(p)) for i, p in enumerate(prompts)]
    eng.submit(es, version=0)
    toks = {e.uid: [] for e in es}
    while eng.active_uids():
        for ev in checked_step(eng):
            toks[ev.uid].append(ev.token)
    return eng, toks


_RAGGED_PROMPTS = [[1, 2, 3, 4, 5, 6, 7, 8, 9, 2], [3, 1, 4], [1, 5, 9, 2, 6],
                   [2, 7, 1, 8, 2, 8, 1], [1, 2]]


def test_packed_prefill_greedy_token_identity():
    """Packed ragged prefill must produce byte-for-byte the same greedy
    token streams as the bucketed dense-prefill path: segment masking +
    per-segment positions make each packed prefix's KV identical to a
    solo prefill."""
    _, base = _greedy_stream(make_slot, _RAGGED_PROMPTS)
    eng, packed = _greedy_stream(make_slot_packed, _RAGGED_PROMPTS)
    assert packed == base, (packed, base)
    assert eng.prefill_launches == 1        # one launch for the whole wave


def test_packed_prefill_one_launch_per_fill_wave():
    """N waves of ragged submits = exactly N packed launches, versus the
    bucketed path which launches once per wave too but at kb x width
    padded cost; the counter is the roofline metric smoke rows pin."""
    eng = make_slot_packed()
    eng.temperature = 0.0
    for wave, plens in enumerate([(9, 3, 5), (7, 2)]):
        eng.submit([BufferEntry(uid=10 * wave + i, prompt=[1] * n + [2 + i])
                    for i, n in enumerate(plens)], version=0)
        assert eng.prefill_launches == wave + 1
        eng.interrupt()
    assert eng.cache_stats()["prefill_launches"] == 2.0


def test_fused_sampling_greedy_token_identity():
    """Fused (streaming) greedy sampling must match the two-pass
    argmax-over-full-logits path exactly, including first-occurrence
    tie-breaks, and must report the same logprobs."""
    eng_b = make_slot(capacity=8)
    eng_f = make_slot_fused(capacity=8)
    for eng in (eng_b, eng_f):
        eng.temperature = 0.0
    out = {}
    for name, eng in (("base", eng_b), ("fused", eng_f)):
        es = [BufferEntry(uid=i, prompt=list(p))
              for i, p in enumerate(_RAGGED_PROMPTS)]
        eng.submit(es, version=0)
        toks = {e.uid: [] for e in es}
        lps = {e.uid: [] for e in es}
        while eng.active_uids():
            for ev in checked_step(eng):
                toks[ev.uid].append(ev.token)
                lps[ev.uid].append(ev.logprob)
        out[name] = (toks, lps)
    assert out["base"][0] == out["fused"][0], out
    for uid, ref in out["base"][1].items():
        for a, b in zip(ref, out["fused"][1][uid]):
            assert abs(a - b) < 1e-4, (uid, a, b)


def test_int8_kv_decode_stays_close_to_fp():
    """int8 pages are lossy but bounded: the quantized engine completes
    every rollout and its early greedy tokens (decoding off freshly
    quantized prefill pages) match fp — gross quantization bugs flip the
    very first token."""
    _, base = _greedy_stream(make_slot, _RAGGED_PROMPTS, max_gen=4)
    eng, quant = _greedy_stream(make_slot_int8, _RAGGED_PROMPTS, max_gen=4)
    assert set(quant) == set(base)
    first_match = sum(quant[u][0] == base[u][0] for u in base)
    assert first_match == len(base), (quant, base)
    assert eng.kv_quant == "int8"
    _drained_pool_is_clean(eng)


def test_int8_scale_planes_follow_cow_and_migration():
    """Per-page scale planes must travel with their pages: COW copies the
    scale row to the new page, and export->import lands the scales on the
    destination pool so a migrated entry keeps decoding identically."""
    import numpy as np
    src = make_slot_int8(capacity=2)
    src.temperature = 0.0
    # shared prompt => shared pages => COW on divergence
    src.submit(group_entries(2, prompt_len=10), version=0)
    for _ in range(3):
        checked_step(src)
    assert src.cache_stats()["cow_copies"] >= 1
    uid = src.active_uids()[0]
    handle = src.export_entry(uid)
    assert handle["kv_quant"] == "int8"
    ex = handle["kv"]
    np.testing.assert_array_equal(
        handle["scales_k"], np.asarray(src.kv_scales["k"][:, ex.pages]))
    dst = make_slot_int8(capacity=2)
    assert dst.import_entry(handle)
    pages = list(dst.kv.tables[uid])
    np.testing.assert_array_equal(
        np.asarray(dst.kv_scales["k"][:, pages]), handle["scales_k"])
    np.testing.assert_array_equal(
        np.asarray(dst.cache["k"][:, pages]), handle["pages_k"])
    # fp pool refuses int8 bytes (and vice versa)
    assert not make_slot(capacity=2).import_entry(handle)
    src.discard_entry(uid)
    run_to_completion(dst)
    run_to_completion(src)
    _drained_pool_is_clean(dst)


def _oracle_checked(eng, seen):
    """Wrap ``eng``'s decode program: every call is checked against the
    gathered-view oracle (``kernels.ref.paged_decode_step_ref``) on copies
    of the same inputs, and leaves the pool it was handed consumed by
    donation.  ``seen`` collects (table width, kv_len % page) per call."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    program = eng._paged_decode
    oracle = jax.jit(functools.partial(ref.paged_decode_step_ref, eng.model))

    def checked(params, token, cache, bt, kv_len, key):
        inputs = jax.tree.map(jnp.copy, (cache, eng.kv_scales))
        sampled, lp, new_cache, new_scales = program(params, token, cache,
                                                     bt, kv_len, key)
        assert all(a.is_deleted() for a in jax.tree.leaves(cache)), \
            "the decode program copied the pool instead of consuming it"
        logits, want_cache, want_scales = oracle(params, token, *inputs, bt,
                                                 kv_len)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        want = np.asarray(jnp.argmax(logp, axis=-1))
        np.testing.assert_array_equal(np.asarray(sampled), want)
        np.testing.assert_allclose(
            np.asarray(lp), np.asarray(logp)[np.arange(len(want)), want],
            atol=1e-4)
        # every page but the garbage page (inactive slots' writes collide)
        for got, ref_ in ((new_cache, want_cache), (new_scales, want_scales)):
            for name in got:
                np.testing.assert_array_equal(np.asarray(got[name][:, 1:]),
                                              np.asarray(ref_[name][:, 1:]))
        act = np.flatnonzero(eng.slots.active)
        seen.update((int(bt.shape[1]), int(n) % eng.page_size)
                    for n in np.asarray(kv_len)[act])
        return sampled, lp, new_cache, new_scales

    eng._paged_decode = checked
    return eng


def _oracle_case(case, eng):
    """Drive ``eng`` (greedy) through one scenario; returns events."""
    evs = []
    if case == "widths":                # table widths 1, 2 and 4 pages
        eng.submit([BufferEntry(uid=i, prompt=[1 + i] * n)
                    for i, n in enumerate((3, 9, 14))], version=0)
        evs += run_to_completion(eng)
    elif case == "cow":                 # shared prompt, divergent writes
        eng.submit(group_entries(3, prompt_len=10), version=0)
        evs += run_to_completion(eng)
    elif case == "inactive":            # 2 of 4 slots write garbage
        eng.submit(entries(2, prompt_len=16), version=0)
        evs += run_to_completion(eng)
    elif case == "resume":              # interrupt, resume resident pages
        es = [BufferEntry(uid=i, prompt=[1, 2 + i, 3] * 5)
              for i in range(4)]
        eng.submit(es, version=0)
        gen = {e.uid: [] for e in es}
        for _ in range(3):
            for ev in checked_step(eng):
                gen[ev.uid].append(ev.token)
        back = eng.interrupt(eng.active_uids()[:2])
        for _ in range(2):
            for ev in checked_step(eng):
                gen[ev.uid].append(ev.token)
        eng.submit([BufferEntry(uid=u, prompt=list(es[u].prompt),
                                generated=list(gen[u])) for u in back],
                   version=0)
        assert eng.cache_stats()["resumed_without_prefill"] == len(back)
        evs += run_to_completion(eng)
    elif case == "migrate":             # export/import after donated steps
        eng.submit(entries(2, prompt_len=12), version=0)
        for _ in range(3):
            evs += checked_step(eng)
        dst = _oracle_checked(make_slot(capacity=CAPACITY,
                                        max_gen=MAX_TOTAL - 16,
                                        kv_quant=eng.kv_quant), set())
        dst.temperature = 0.0
        assert dst.import_entry(eng.export_entry(0))
        eng.discard_entry(0)
        evs += run_to_completion(dst) + run_to_completion(eng)
        _drained_pool_is_clean(dst)
    return evs


@pytest.mark.parametrize("quant", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("case", ["widths", "cow", "inactive", "resume",
                                  "migrate"])
def test_paged_decode_in_place_matches_gathered_view(case, quant):
    """The in-place decode program (attention on the pool through the
    block tables, the new rows written straight into their pages, the
    pool donated) computes what the gathered-view step computed: the
    same greedy tokens, logprobs within 1e-4 and the same pages, at every
    table width, on copy-on-write pages, for inactive slots, at page
    offsets 0 and P-1, after a resume on resident pages and on both sides
    of a migration of pages exported after donated steps."""
    seen = set()
    eng = _oracle_checked(make_slot(capacity=CAPACITY,
                                    max_gen=MAX_TOTAL - 16, kv_quant=quant),
                          seen)
    eng.temperature = 0.0
    _oracle_case(case, eng)
    _drained_pool_is_clean(eng)
    if case == "widths":
        assert {w for w, _ in seen} == {1, 2, 4}
        assert {0, eng.page_size - 1} <= {o for _, o in seen}
    if case == "cow":
        assert eng.cache_stats()["cow_copies"] >= 2


def test_resident_resume_rate_counts_attempts():
    """resume_attempts counts every try_resume of a previously
    interrupted uid — hits AND misses — so resident_resume_rate is a real
    hit rate, not resumed/resumed."""
    eng = make_slot()
    es = entries(2)
    eng.submit(es, version=0)
    checked_step(eng)
    eng.interrupt()
    # uid 0 resumes resident; uid 1's pages get evicted first => miss
    # (evict via the memory-pressure path, which keeps the interrupted
    # mark — an explicit release_seq is a deliberate drop, not a miss)
    del eng.kv._resident[es[1].uid]
    eng.kv._drop(es[1].uid)
    eng.submit([BufferEntry(uid=e.uid, prompt=list(e.prompt),
                            generated=[2]) for e in es], version=0)
    st = eng.cache_stats()
    assert st["resume_attempts"] == 2.0
    assert st["resumed_without_prefill"] == 1.0
    assert st["resident_resume_rate"] == pytest.approx(0.5)
    assert st["pool_capacity_tokens"] == (eng.num_pages - 1) * eng.page_size


@pytest.mark.parametrize("mode", [Mode.ON_POLICY, Mode.PARTIAL])
def test_paged_resume_without_reprefill(mode):
    """Interrupted entries keep pages resident: resubmitting scavenged
    entries runs ZERO new prefill tokens (observable via cache_stats),
    and the pool is clean after the resumed rollout drains."""
    eng = make_slot()
    buf = StatefulRolloutBuffer(mode)
    uids = buf.load_prompts([[1, 2, 3, 4, 5], [1, 2, 3, 4, 5]])
    buf.mark_running(uids)
    eng.submit(buf.running(), version=0)
    for _ in range(2):
        for ev in checked_step(eng):
            buf.record_tokens(ev.uid, [ev.token], [ev.logprob], 0)
            if ev.done:
                buf.mark_done(ev.uid, ev.finish_reason)
    for uid in eng.interrupt():
        buf.scavenge(uid)
    run_before = eng.cache_stats()["prefill_tokens_run"]
    saved_before = eng.cache_stats()["prefill_tokens_saved"]
    assert eng.cache_stats()["resident_seqs"] == 2
    resumed = buf.pending()
    buf.mark_running([e.uid for e in resumed])
    eng.submit(resumed, version=1)
    st = eng.cache_stats()
    assert st["prefill_tokens_run"] == run_before, "resume re-ran prefill"
    assert st["resumed_without_prefill"] == 2
    assert st["prefill_tokens_saved"] > saved_before
    for ev in run_to_completion(eng):
        buf.record_tokens(ev.uid, [ev.token], [ev.logprob], 1)
        if ev.done:
            buf.mark_done(ev.uid, ev.finish_reason)
    buf.check_invariants()
    _drained_pool_is_clean(eng)


def test_paged_oversubscribed_pool_with_shared_prefixes():
    """A pool too small for CAPACITY dense sequences still serves a
    shared-prompt group: the prefix pages are mapped, not copied.  Dense
    sizing here would need capacity * ceil(31/16) = 8 pages; sharing fits
    in 6 (2 prefix + at most 4 COW write pages)."""
    plen = 25                       # pre = 24 rows = 1.5 pages of 16
    eng = make_slot(num_pages=7)    # 6 usable + garbage page
    assert eng.paged
    eng.submit(group_entries(CAPACITY, plen), version=0)
    evs = run_to_completion(eng)
    assert sum(1 for e in evs if e.done) == CAPACITY
    st = eng.cache_stats()
    assert st["prefill_tokens_run"] == plen - 1
    assert st["prefill_tokens_saved"] == (CAPACITY - 1) * (plen - 1)
    _drained_pool_is_clean(eng)


def test_paged_strict_sync_invalidates_stale_kv():
    """kv_retain_across_sync=False: a weight sync drops pre-sync resident
    prefixes, so scavenged entries re-prefill under the fresh policy
    (exact dense semantics — the on-policy re-roll setting)."""
    eng = make_slot(kv_retain_across_sync=False)
    e = BufferEntry(uid=0, prompt=[1, 2, 3, 4])
    eng.submit([e], version=0)
    checked_step(eng)
    eng.interrupt()
    run_before = eng.cache_stats()["prefill_tokens_run"]
    eng.sync_weights(1)
    assert eng.cache_stats()["pages_in_use"] == 0, "stale resident kept"
    eng.submit([e], version=1)
    st = eng.cache_stats()
    assert st["prefill_tokens_run"] > run_before, "resume skipped prefill"
    assert st["resumed_without_prefill"] == 0
    assert st["stale_kv_reuses"] == 0
    run_to_completion(eng)
    _drained_pool_is_clean(eng)


def test_paged_retaining_sync_reuses_and_counts_stale_kv():
    """Default (partial-mode) setting: resident pages survive the sync —
    the paper's cache mechanism — and each reuse of pre-sync KV is
    observable via the stale_kv_reuses counter."""
    eng = make_slot()                       # kv_retain_across_sync=True
    e = BufferEntry(uid=0, prompt=[1, 2, 3, 4])
    eng.submit([e], version=0)
    checked_step(eng)
    eng.interrupt()
    run_before = eng.cache_stats()["prefill_tokens_run"]
    eng.sync_weights(1)
    assert eng.cache_stats()["pages_in_use"] > 0, "resident pages dropped"
    eng.submit([e], version=1)
    st = eng.cache_stats()
    assert st["prefill_tokens_run"] == run_before
    assert st["resumed_without_prefill"] == 1
    assert st["stale_kv_reuses"] == 1
    run_to_completion(eng)
    _drained_pool_is_clean(eng)


def test_paged_pool_pressure_evicts_resident_lru():
    """Resident (interrupted) sequences are reclaimed under pool pressure
    instead of failing the submit."""
    eng = make_slot(num_pages=9)    # 8 usable pages
    eng.submit([BufferEntry(uid=i, prompt=[2 + i] * 20) for i in range(4)],
               version=0)
    checked_step(eng)
    eng.interrupt()                 # 4 resident seqs x 2 pages = full pool
    assert eng.cache_stats()["pages_in_use"] == 8
    eng.submit([BufferEntry(uid=10 + i, prompt=[9 + i] * 20)
                for i in range(4)], version=0)
    st = eng.cache_stats()
    assert st["evictions"] >= 3, st
    run_to_completion(eng)
    eng.kv.check_invariants()


def test_paged_metrics_flow_through_orchestrator():
    """RolloutOrchestrator surfaces prefill-tokens-saved and page-pool
    occupancy for paged engines."""
    from repro.core.orchestrator import RolloutOrchestrator, SortedRLConfig
    from repro.core.policy import make_policy
    eng = make_slot()
    buf = StatefulRolloutBuffer(Mode.ON_POLICY)
    cfg = SortedRLConfig(rollout_batch=CAPACITY, group_size=1,
                         update_batch=CAPACITY, max_gen_len=MAX_GEN)
    orch = RolloutOrchestrator(eng, buf, cfg, make_policy("baseline"),
                               lambda req: None)
    orch.run_group([[1, 2, 3]] * CAPACITY)      # one shared-prompt group
    s = orch.metrics.summary()
    assert s["prefill_tokens_saved"] == (CAPACITY - 1) * 2
    assert 0.0 < s["page_occupancy_peak"] <= 1.0


# -- EngineGroup (multi-replica) cases ----------------------------------------
#
# The group fixtures above already run the whole EngineProtocol contract
# against EngineGroup; these cases additionally pin the group-only
# behaviour: deterministic event merging, conservation across the merge,
# and home-affinity resume vs work-stealing migration.

def test_group_event_merge_order_is_replica_major():
    """Merged step events are the per-replica streams concatenated in
    replica order (each ascending-slot), and per-uid routing is stable."""
    eng = make_group_sim()
    eng.submit(entries(CAPACITY), version=0)
    by_replica = [list(r.active_uids()) for r in eng.replicas]
    assert sorted(u for uids in by_replica for u in uids) == list(
        range(CAPACITY))
    expect = [u for uids in by_replica for u in uids]
    evs = checked_step(eng)
    assert [ev.uid for ev in evs] == expect
    # stable while resident: the merged order only loses finished uids
    while eng.active_uids():
        live = set(eng.active_uids())
        evs = checked_step(eng)
        assert [ev.uid for ev in evs] == [u for u in expect if u in live]


def test_group_conservation_across_replicas():
    """Replica-failure-free conservation: with every replica healthy, an
    oversubscribed workload drains with each uid finishing exactly once
    across the merged streams, and the replica loads sum to the total."""
    n = 3 * CAPACITY
    eng = make_group_sim()
    buf = StatefulRolloutBuffer(Mode.PARTIAL)
    buf.load_prompts([[1, 2 + i % 7] for i in range(n)])
    done_counts = {}
    steps = 0
    while buf.pending() or buf.running():
        batch = buf.pending()[:eng.free_slots()]
        if batch:
            buf.mark_running([e.uid for e in batch])
            eng.submit(batch, version=0)
        assert sum(len(r.active_uids()) for r in eng.replicas) == \
            len(buf.running())
        for ev in checked_step(eng):
            buf.record_tokens(ev.uid, [ev.token], [ev.logprob], 0)
            if ev.done:
                done_counts[ev.uid] = done_counts.get(ev.uid, 0) + 1
                buf.mark_done(ev.uid, ev.finish_reason)
        steps += 1
        assert steps < 10_000
    assert done_counts == {uid: 1 for uid in range(n)}
    assert eng.free_slots() == CAPACITY
    buf.check_invariants()


def test_group_home_affinity_resume_zero_reprefill():
    """Interrupted entries route back to their home replica where the KV
    pages stayed resident: the group resumes them with ZERO re-prefill,
    exactly like a single paged engine."""
    eng = make_group_slot()
    buf = StatefulRolloutBuffer(Mode.PARTIAL)
    uids = buf.load_prompts([[1, 2, 3, 4, 5], [6, 7, 8, 9, 2]])
    buf.mark_running(uids)
    eng.submit(buf.running(), version=0)
    homes = {u: dict(eng._home)[u] for u in uids}
    assert sorted(homes.values()) == [0, 1], "balancer did not spread"
    for _ in range(2):
        for ev in checked_step(eng):
            buf.record_tokens(ev.uid, [ev.token], [ev.logprob], 0)
            if ev.done:
                buf.mark_done(ev.uid, ev.finish_reason)
    for uid in eng.interrupt():
        buf.scavenge(uid)
    st = eng.cache_stats()
    run_before = st["prefill_tokens_run"]
    assert st["resident_seqs"] == 2
    resumed = buf.pending()
    buf.mark_running([e.uid for e in resumed])
    eng.submit(resumed, version=0)
    st = eng.cache_stats()
    assert st["prefill_tokens_run"] == run_before, "resume re-ran prefill"
    assert st["resumed_without_prefill"] == len(resumed)
    assert st["steal_count"] == 0
    assert all(dict(eng._home)[u] == homes[u] for u in
               [e.uid for e in resumed]), "resume left its home replica"
    for ev in run_to_completion(eng):
        buf.record_tokens(ev.uid, [ev.token], [ev.logprob], 0)
        if ev.done:
            buf.mark_done(ev.uid, ev.finish_reason)
    buf.check_invariants()
    for r in eng.replicas:
        r.kv.check_invariants()


def test_group_steal_migrates_when_home_is_full():
    """Work stealing: a scavenged entry whose home replica is saturated
    migrates to another replica (counted in steal_count), re-prefills
    there, and still finishes within its budget."""
    eng = make_group_slot()
    buf = StatefulRolloutBuffer(Mode.PARTIAL)
    uids = buf.load_prompts([[1, 2, 3, 4, 5], [6, 7, 8, 9, 2]])
    buf.mark_running(uids)
    eng.submit(buf.running(), version=0)
    home0 = dict(eng._home)[uids[0]]
    for _ in range(2):
        for ev in checked_step(eng):
            buf.record_tokens(ev.uid, [ev.token], [ev.logprob], 0)
            if ev.done:
                buf.mark_done(ev.uid, ev.finish_reason)
    for uid in eng.interrupt():
        buf.scavenge(uid)
    # saturate uid0's home replica with fresh work
    fillers = [BufferEntry(uid=100 + i, prompt=[3, 1, 4, 1 + i])
               for i in range(3)]
    eng.submit(fillers, version=0)
    assert eng.replicas[home0].free_slots() == 0
    run_before = eng.cache_stats()["prefill_tokens_run"]
    victim = buf.entries[uids[0]]
    prefix = victim.gen_len
    buf.mark_running([victim.uid])
    eng.submit([victim], version=0)
    st = eng.cache_stats()
    assert st["steal_count"] == 1
    assert dict(eng._home)[victim.uid] != home0, "steal stayed home"
    assert st["prefill_tokens_run"] > run_before, \
        "migrated resume cannot reuse the home replica's pages"
    # the abandoned residency must be dropped, not left to rot in the
    # old home's pool until LRU pressure reaches it
    assert victim.uid not in eng.replicas[home0].kv.tables, \
        "steal left dead resident pages on the old home replica"
    new = sum(1 for ev in run_to_completion(eng) if ev.uid == victim.uid)
    assert 1 <= prefix + new <= MAX_GEN
    for r in eng.replicas:
        r.kv.check_invariants()
