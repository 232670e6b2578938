"""The length adapter on a tiny SlotEngine under the sorted policy."""
from repro.core.buffer import Mode, StatefulRolloutBuffer
from repro.core.orchestrator import RolloutOrchestrator, SortedRLConfig
from repro.core.policy import make_policy
from repro.models.model import build_model
from repro.rollout.engine import SlotEngine

from bench.adapter import LengthAdapter
from bench.tests.tiny import tiny_cell
from bench.traffic import Traffic
from bench.weights import make_weights


def test_adapter_ends_each_trajectory_at_its_drawn_length():
    cell = tiny_cell()
    c, mcfg, mix = cell["config"], cell["mcfg"], cell["mix"]
    model = build_model(mcfg)
    params = make_weights(model, c, 5)
    # eos -1 is never sampled, so only the drawn lengths end trajectories
    eng = SlotEngine(model, lambda: params, capacity=mix["slots"],
                     max_total_len=mix["max_total_len"],
                     max_gen_len=mix["max_total_len"], eos_id=-1,
                     temperature=1.0, seed=5)
    raw = {}
    inner_step = eng.step

    def step():
        evs = inner_step()
        for ev in evs:
            raw.setdefault(ev.uid, []).append((ev.token, ev.logprob))
        return evs
    eng.step = step
    lengths = {}
    ad = LengthAdapter(eng, lengths.__getitem__)
    buf = StatefulRolloutBuffer(Mode.PARTIAL)
    consumed = []

    def train_fn(req):
        consumed.extend(req.entries)
    orch = RolloutOrchestrator(
        ad, buf, SortedRLConfig(mode=Mode.PARTIAL,
                                rollout_batch=mix["slots"],
                                group_size=mix["group_size"],
                                update_batch=mix["update_batch"],
                                max_gen_len=mix["max_total_len"]),
        make_policy("sorted"), train_fn)
    traffic = Traffic(mix, 5)
    for _ in range(2):
        prompts, metas, lens = traffic.next_group()
        lengths.update(zip(buf.load_prompts(prompts, metas), lens))
        while not buf.group_clear():
            orch.rollout_until_harvest()
            remaining = len(buf.unconsumed()) - len(buf.done())
            orch.train_ready(final=(remaining == 0))
        buf.advance_group()
    assert len(consumed) == 2 * mix["slots"] * mix["group_size"]
    assert orch.metrics.harvests > 0           # sorted harvests happened
    assert any(e.interruptions for e in consumed)
    for e in consumed:
        assert len(e.generated) == lengths[e.uid]
        assert e.finish_reason == "length"
        assert list(zip(e.generated, e.logprobs)) == raw[e.uid]
    assert not eng.active_uids()
    eng.kv.check_invariants()
    assert eng.kv.pool.pages_in_use == 0
