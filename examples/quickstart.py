"""Quickstart: SortedRL scheduling in ~40 lines.

Picks the length-aware policy from the registry, hands it to the
orchestrator, and runs it against the discrete-event engine on the
paper's workload shape, printing the bubble ratio + micro-curriculum.
Swap the policy name ("baseline", "posthoc_sort", "pipelined", ...) to
compare strategies — the orchestration mechanics are shared.

The second half re-runs the same workload with rollout sharded over four
engine replicas behind an EngineGroup (length-aware load balancing) —
the orchestrator and policy are reused UNCHANGED; only the engine
changes.  `RLSession.from_config(SessionConfig(num_replicas=4))` wires
the same thing declaratively.

  PYTHONPATH=src python examples/quickstart.py
"""
import random

from repro.compile_cache import enable_compile_cache
from repro.core.buffer import Mode, StatefulRolloutBuffer
from repro.core.orchestrator import RolloutOrchestrator, SortedRLConfig
from repro.core.policy import make_policy
from repro.rollout.group import EngineGroup
from repro.rollout.sim import SimEngine, lognormal_lengths


def main():
    rng = random.Random(0)
    prompts = [[1] * rng.randint(32, 128) for _ in range(512)]

    engine = SimEngine(capacity=128, max_gen_len=8192,
                       length_sampler=lognormal_lengths(median=2000,
                                                        sigma=1.5,
                                                        max_len=8192))
    buffer = StatefulRolloutBuffer(Mode.PARTIAL)
    cfg = SortedRLConfig(mode=Mode.PARTIAL, rollout_batch=128, group_size=4,
                         update_batch=128, max_gen_len=8192)

    batches = []

    def train_fn(req):
        lens = [e.gen_len for e in req.entries]
        batches.append(lens)
        print(f"update v{req.version}: {len(req.entries)} trajectories, "
              f"mean len {sum(lens)/len(lens):.0f} "
              f"(sorted: {lens == sorted(lens)})")

    orch = RolloutOrchestrator(engine, buffer, cfg, make_policy("sorted"),
                               train_fn)
    orch.run_group(prompts)
    print("\nrollout metrics:", orch.metrics.summary())
    print("micro-curriculum batch means:",
          [round(sum(b) / len(b)) for b in batches])

    # the SAME 512 prompts, rollout sharded over 4 data-parallel replicas
    # — the orchestrator and policy run unchanged against the EngineGroup
    # facade.  A shared length_table pins each trajectory's hidden length
    # to its uid, so lengths stay a property of the prompt rather than of
    # whichever replica serves it (routing-invariant workload).
    sample = lognormal_lengths(median=2000, sigma=1.5, max_len=8192)
    lengths = {uid: sample(rng) for uid in range(len(prompts))}
    group = EngineGroup([
        SimEngine(capacity=32, max_gen_len=8192, seed=i,
                  length_table=lengths)
        for i in range(4)])
    orch4 = RolloutOrchestrator(group, StatefulRolloutBuffer(Mode.PARTIAL),
                                cfg, make_policy("sorted"), lambda req: None)
    orch4.run_group(prompts)
    m = orch4.metrics.summary()
    print(f"\n4-replica rollout: bubble={m['bubble_ratio']} "
          f"replica_bubble={m['replica_bubble_ratio']} "
          f"busy_replicas={m['replica_busy']} steals={m['steal_count']}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
