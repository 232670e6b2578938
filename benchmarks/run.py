"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  fig1a_breakdown/*   latency breakdown (rollout dominance, Fig. 1a/1c)
  fig5_throughput/*   throughput + bubble ratio per strategy (Fig. 5, Eq. 4)
  fig6a_ablation/*    grouped-rollout / post-hoc-sort ablations (Fig. 6a)
  fig6b_group_size/*  group-size sensitivity (Fig. 6b)
  fill_policy/*       beyond-paper slot-fill study
  policy_sweep/*      every registered SchedulerPolicy, by name
  prefix_share/*      paged-KV-cache GRPO prefix sharing + resume rows
  replicas/*          EngineGroup data-parallel rollout: bubble vs replicas
  overlap/*           rollout/update overlap: serialized vs streaming trainer
  serving/*           always-on serving tier: multi-tenant admission rows
  autoscale/*         feedback-driven fleet autoscaling: scale events from
                      windowed bubble / queue-depth signals
  fig3_logic_rl/*     real RL token-efficiency on K&K (Fig. 3, quick mode)
  roofline_table/*    per (arch x shape) roofline terms (§Roofline)
  roofline/*          kernel/memory roofline rows: packed prefill, fused
                      sampling, int8 KV pages (smoke mode; §Kernel &
                      memory roofline in the README)

Full-scale variants: bench_logic_rl --full, repro.launch.dryrun --all.

``--smoke``: seconds-scale pass (reduced simulator workloads, no jit-heavy
roofline or real-RL sections) — the default verification path; full runs
are opt-in.  The smoke pass sweeps every registered scheduling policy by
name and runs examples/quickstart.py end to end (in this process), so a
registry entry (or the quickstart) that rots fails the smoke gate.

``--json PATH``: additionally write the rows as structured JSON
({name, us_per_call, derived} plus the git sha) — the artifact the CI
smoke gate diffs against the checked-in ``BENCH_smoke.json`` baseline
(see benchmarks/compare.py).
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import runpy
import subprocess
import sys
import time

from repro.compile_cache import enable_compile_cache


def quickstart_smoke_row() -> str:
    """Run examples/quickstart.py's main() in this process (one process
    holds the chip) and check its captured output."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    quickstart = runpy.run_path(os.path.join(root, "examples",
                                             "quickstart.py"))
    out = io.StringIO()
    t0 = time.time()
    with contextlib.redirect_stdout(out):
        quickstart["main"]()
    dt = time.time() - t0
    if "micro-curriculum batch means:" not in out.getvalue():
        print(out.getvalue(), file=sys.stderr)
        raise RuntimeError("examples/quickstart.py smoke check failed")
    return f"smoke/quickstart,{dt*1e6:.0f},ok=1"


def git_sha() -> str:
    try:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run(["git", "rev-parse", "HEAD"], cwd=root,
                             capture_output=True, text=True, timeout=10)
        return out.stdout.strip() if out.returncode == 0 else "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def rows_to_json(rows, smoke: bool) -> dict:
    parsed = []
    for r in rows:
        parts = r.split(",", 2)
        # some sections (roofline_table) emit wide CSV rows whose second
        # field is not a timing — keep them with us_per_call=None rather
        # than crashing after the whole run completed
        try:
            us = float(parts[1])
        except (IndexError, ValueError):
            us = None
        parsed.append({"name": parts[0], "us_per_call": us,
                       "derived": ",".join(parts[2:]) if us is not None
                       else ",".join(parts[1:])})
    return {"git_sha": git_sha(), "smoke": smoke, "rows": parsed}


def json_path_from_argv(argv) -> str:
    """Validate --json PATH up front — failing after the full benchmark
    run would throw the results away."""
    if "--json" not in argv:
        return ""
    i = argv.index("--json")
    if i + 1 >= len(argv) or argv[i + 1].startswith("--"):
        raise SystemExit("benchmarks.run: --json requires a PATH argument")
    return argv[i + 1]


def main() -> None:
    enable_compile_cache()
    from benchmarks import (bench_ablation, bench_autoscale, bench_breakdown,
                            bench_logic_rl, bench_overlap, bench_prefix_share,
                            bench_replicas, bench_serving, bench_throughput,
                            roofline)
    json_path = json_path_from_argv(sys.argv)
    smoke = "--smoke" in sys.argv
    if smoke:
        # ablation.main carries the acceptance-pinned fig6a/6b rows AND the
        # all-registered-policies sweep
        sections = (("breakdown", bench_breakdown.main),
                    ("throughput", lambda: bench_throughput.main(smoke=True)),
                    ("ablation", bench_ablation.main),
                    ("prefix_share",
                     lambda: bench_prefix_share.main(smoke=True)),
                    ("replicas", lambda: bench_replicas.main(smoke=True)),
                    ("overlap", lambda: bench_overlap.main(smoke=True)),
                    ("serving", lambda: bench_serving.main(smoke=True)),
                    ("autoscale", lambda: bench_autoscale.main(smoke=True)),
                    ("roofline", roofline.smoke),
                    ("quickstart", lambda: [quickstart_smoke_row()]))
    else:
        sections = (("breakdown", bench_breakdown.main),
                    ("throughput", bench_throughput.main),
                    ("ablation", bench_ablation.main),
                    ("prefix_share", bench_prefix_share.main),
                    ("replicas", bench_replicas.main),
                    ("overlap", bench_overlap.main),
                    ("serving", bench_serving.main),
                    ("autoscale", bench_autoscale.main),
                    ("quickstart", lambda: [quickstart_smoke_row()]),
                    ("roofline", roofline.main))
    rows = []
    for mod, fn in sections:
        t0 = time.time()
        rows.extend(fn())
        print(f"# {mod} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if "--skip-rl" not in sys.argv and not smoke:
        t0 = time.time()
        rows.extend(bench_logic_rl.main(quick=True))
        print(f"# logic_rl done in {time.time()-t0:.1f}s", file=sys.stderr)
    print("name,us_per_call,derived")
    for r in rows:
        print(r)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(rows_to_json(rows, smoke), f, indent=1)
        print(f"# wrote {json_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
