"""End-to-end RL training driver (paper §4.2 at CPU scale): SFT warm-up,
then Reinforce++ with the chosen scheduling policy on Knights & Knaves,
built by the one-call session builder.

  PYTHONPATH=src python examples/train_logic_rl.py --policy sorted \
      --mode on_policy --groups 4
"""
import argparse
import json

from repro.compile_cache import enable_compile_cache
from repro.core.buffer import Mode
from repro.core.policy import available_policies
from repro.rl.session import RLSession, SessionConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", "--strategy", dest="policy",
                    default="sorted", choices=available_policies())
    ap.add_argument("--mode", default="on_policy",
                    choices=["on_policy", "partial"])
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--rollout-batch", type=int, default=32)
    ap.add_argument("--update-batch", type=int, default=32)
    ap.add_argument("--group-size", type=int, default=2)
    ap.add_argument("--sft-steps", type=int, default=150)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cfg = SessionConfig(
        task="logic", policy=args.policy, mode=Mode(args.mode),
        rollout_batch=args.rollout_batch, update_batch=args.update_batch,
        group_size=args.group_size, n_groups=args.groups,
        sft_steps=args.sft_steps, seed=args.seed)
    out = RLSession.from_config(cfg).run()
    print("final eval:", out["final_eval"])
    print("rollout:", out["rollout_metrics"])
    for ev in out["evals"]:
        print("  eval", ev)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1, default=str)


if __name__ == "__main__":
    enable_compile_cache()
    main()
