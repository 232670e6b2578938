"""The span reducer: idle gaps split by overlap over the program's spans."""
import os
import shutil

import pytest

from bench import span_reduce as S
from bench import trace_reduce as T
from bench.run import load_reader

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny.xplane.pb")
ms = 1_000_000
TH = (0, 0)


def hand_trace():
    prog = [("engine.step", 0, 8 * ms), ("engine.step.prepare", 0, 1 * ms),
            ("engine.step.launch", 1 * ms, 2 * ms),
            ("engine.step.wait", 2 * ms, 7 * ms),
            ("engine.step.events", 7 * ms, 8 * ms),
            ("sched.apply_events", 8 * ms, 9 * ms),
            ("sched.harvest_check", 9 * ms, 9.5 * ms),
            ("sched.train_ready", 9.5 * ms, 14 * ms),
            ("trainer.update", 10 * ms, 13.5 * ms),
            ("trainer.step", 10.5 * ms, 11 * ms)]
    return {
        "device": {"XLA Modules": [("jit__paged_decode_fn(3)", 1.2 * ms,
                                    6.9 * ms),
                                   ("jit_train_step(7)", 11 * ms, 13 * ms)],
                   "XLA Ops": []},
        "host": [("decode", 0, 10 * ms), ("update", 10 * ms, 14 * ms),
                 ("submit", 14.5 * ms, 15 * ms)],
        "program": [(n, a, b, TH) for n, a, b in prog],
    }


def test_gaps_split_by_overlap():
    r = S.reduce(hand_trace())
    idle = dict(r["idle_by_span"])
    want = {"engine.step.prepare": 1.0, "engine.step.launch": 0.2,
            "engine.step.wait": 0.1, "engine.step.events": 1.0,
            "sched.apply_events": 1.0, "sched.harvest_check": 0.5,
            "sched.train_ready": 1.0, "trainer.update": 1.0,
            "trainer.step": 0.5, "none": 0.5, "harness:submit": 0.5}
    assert idle == pytest.approx({k: v * 1e-3 for k, v in want.items()})
    # the parts sum to the stretch's idle time, as trace_reduce counts it
    assert r["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["window_s"] == pytest.approx(0.015)


def test_span_sums_and_step_host_time():
    r = S.reduce(hand_trace())
    sp = r["spans"]
    assert sp["engine.step"] == pytest.approx([1, 0.008, 0.0])
    assert sp["sched.train_ready"] == pytest.approx([1, 0.0045, 0.001])
    assert sp["trainer.update"][2] == pytest.approx(0.003)
    assert r["engine_step_host_s"] == pytest.approx([0.003])
    assert r["harness"]["decode"] == pytest.approx([1, 0.010])


def test_recorded_trace_without_program_spans():
    # tiny.xplane.pb has only the harness's spans: all its idle time falls
    # under them or under none, and the readers report nothing
    r = S.reduce(S.load(TINY))
    assert r["spans"] == {} and r["engine_step_host_s"] == []
    assert r["idle_by_span"]
    assert all(k == "none" or k.startswith("harness:")
               for k, _ in r["idle_by_span"])
    assert r["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"])


def test_readers_report_nothing_without_program_spans(tmp_path,
                                                      monkeypatch):
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(TINY, d / "tiny.xplane.pb")
    monkeypatch.setattr(S, "TRACE_DIR", str(tmp_path))
    ctx = {"trace": T.reduce_dir(str(tmp_path))}
    for name in ("engine.device_idle_share", "sched.device_idle_share",
                 "trainer.device_idle_share", "engine.step_host_ms",
                 "sched.step_host_ms"):
        assert load_reader(name)(ctx) is None
        assert load_reader(name)({"trace": None}) is None
    # a trace file that is not the run's own is not read
    assert S.for_run({"trace": {"window_s": 1.0, "busy_s": 0.5}}) is None


def test_readers_on_a_reduction(monkeypatch):
    r = S.reduce(hand_trace())
    monkeypatch.setattr(S, "for_run", lambda ctx: r)
    ctx = {"trace": {}}
    pct = 100 / 15
    assert load_reader("engine.device_idle_share")(ctx) == pytest.approx(
        2.3 * pct)
    assert load_reader("sched.device_idle_share")(ctx) == pytest.approx(
        2.5 * pct)
    assert load_reader("trainer.device_idle_share")(ctx) == pytest.approx(
        1.5 * pct)
    assert load_reader("engine.step_host_ms")(ctx) == pytest.approx(3.0)
    # self time of apply_events 1 + harvest_check 0.5 + train_ready 1.0
    assert load_reader("sched.step_host_ms")(ctx) == pytest.approx(2.5)
