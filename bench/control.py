#!/usr/bin/env python3
"""Readings that set the limits of ``correct`` (``bench/check.py``).

For each seed, in one process: the cell's set-up (build, warm-up, the drive
through the checked first updates) exactly as a benchmark run makes it,
then, against the float32 reference:

* ``program``: the program's own readings (the lower end of each limit);
* ``control_fp8``: the reference put in the program's place and computed
  one precision below the configurations' bfloat16 (float8 e4m3 operands
  in every matrix product);
* ``fault_half_batch``: the reference put in the program's place, each
  update taken on half of its rows with the mean over the rest;
* ``fault_token_altered``: the first served token of every scored row
  replaced by the next id, its recorded logprob kept (a token altered
  where it is produced).

A step that returns its state unchanged reads ``update_gap`` 1 by the
measure's definition and needs no run.

    python3 bench/control.py --workload <name> --seeds 11 12 13 [--faults ...]

Each seed prints one JSON line.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def altered(v0, vocab_size: int):
    out = []
    for r in v0:
        g = list(r["generated"])
        if g:
            g[0] = (g[0] + 1) % vocab_size
        out.append(dict(r, generated=g))
    return out


def readings(c, mcfg, mix, seed, rec, first_step, width,
             faults=("control_fp8", "fault_half_batch",
                     "fault_token_altered")):
    """Every reading of one seed's recorded set-up, as check.gaps dicts."""
    from bench import check
    ref = check.reference_readings(c, mcfg, mix, seed, rec, first_step,
                                   width)
    out = {"program": check.gaps(check.program_readings(rec), ref)}
    if "control_fp8" in faults:
        out["control_fp8"] = check.gaps(check.reference_readings(
            c, mcfg, mix, seed, rec, first_step, width, mode="fp8"), ref)
    if "fault_half_batch" in faults:
        half = [rows[:max(1, len(rows) // 2)] for rows in rec["batches"]]
        out["fault_half_batch"] = check.gaps(check.reference_readings(
            c, mcfg, mix, seed, rec, first_step, width, batches=half), ref)
    if "fault_token_altered" in faults:
        from bench.reference import Reference
        from bench.weights import make_weights
        from repro.models.model import build_model
        v0 = altered(check.served(rec), mcfg.vocab_size)
        p0 = make_weights(build_model(mcfg), c, seed)
        lps = Reference(c).row_logprobs(
            p0, [r["prompt"] + r["generated"] for r in v0], width)
        import numpy as np
        gap = max(float(np.max(np.abs(np.asarray(r["logprobs"])
                                      - x[len(r["prompt"]):])))
                  for r, x in zip(v0, lps) if r["generated"])
        out["fault_token_altered"] = {"lp_gap": gap}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=None,
                    help="readings besides the program's (default: all; "
                    "none given: the program's alone)")
    args = ap.parse_args(argv)
    faults = {} if args.faults is None else {"faults": tuple(args.faults)}
    from bench import run as R
    from bench.model_config import load_model_config
    from bench.traffic import load_mix
    cell = R.cell_of(R.load_benchmark(), args.workload)
    R.devices_or_refuse(cell["chips"], require_tpu=True)
    R.configure_jax()
    c, mcfg = load_model_config(cell["config"])
    mix = load_mix(cell["traffic"])
    clock = R.CompileClock()
    for seed in args.seeds:
        t = time.perf_counter()
        sess, first_step, _ = R.prepare(c, mcfg, mix, seed, clock)
        rec = sess.check_rec
        del sess
        gc.collect()
        out = readings(c, mcfg, mix, seed, rec, first_step,
                       mix["max_total_len"], **faults)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "seconds": time.perf_counter() - t, **out}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
