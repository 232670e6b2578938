"""Math-task RL driver (paper §4.3 analog): integer-answer synthetic
problems with exact-match verification, built by the one-call session
builder — the only difference from the logic driver is ``task="math"``.

  PYTHONPATH=src python examples/train_math_rl.py --groups 2
"""
import argparse

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
    ap.add_argument("--groups", type=int, default=2)
    ap.add_argument("--responses-per-prompt", type=int, default=1)
    ap.add_argument("--advantage", default="reinforce_pp",
                    choices=["reinforce_pp", "grpo"])
    args = ap.parse_args()
    cfg = SessionConfig(
        task="math", policy=args.policy, mode=Mode(args.mode),
        n_groups=args.groups, rollout_batch=16, group_size=2,
        update_batch=16, max_gen_len=8, max_total_len=96, sft_steps=100,
        d_model=96, layers=2, eval_size=32,
        responses_per_prompt=args.responses_per_prompt,
        advantage_kind=args.advantage)
    out = RLSession.from_config(cfg).run()
    print("final eval:", out["final_eval"])
    print("rollout:", out["rollout_metrics"])


if __name__ == "__main__":
    enable_compile_cache()
    main()
