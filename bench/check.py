"""The comparison that decides ``correct``.

The set-up drives the session through its first ``check_updates`` updates
(``bench/run.py``) and records what they produced:

* served tokens under the starting weights, with the logprob the engine
  recorded for each: every token sampled before the first update (the
  paged prefill with GRPO prefix sharing and copy-on-write pages, and the
  paged decode of all the cell's slots at short contexts), and the warm-up
  probe's tokens (``Cell.probe``: all slots decoding at every block-table
  width up to ``max_total_len``, and resumed on resident pages after an
  interrupt);
* each update's batch (tokens, recorded logprobs, rewards, prompt groups)
  and its loss;
* the first update's clipped gradient, per leaf, read from AdamW's first
  moment, and the change of every leaf after the last checked update.

The plain reference (``bench/reference.py``) starts from the same seeded
weights, scores the same tokens and takes the same updates.  Four numbers
are compared, each against its limit in ``bench/limits/<workload>.json``:

``lp_gap``      widest |engine logprob - reference logprob| over the tokens
``loss_gap``    widest |program loss - reference loss| over the updates
``grad_gap``    worst leaf: |program gradient norm - reference's| over the
                larger of the reference leaf's norm and the median leaf's
``update_gap``  the same for the change of the weights over the updates,
                leaving out leaves whose reference gradient is under a
                thousandth of the median leaf's (moved by round-off alone)
"""
from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
NUMBERS = ("lp_gap", "loss_gap", "grad_gap", "update_gap")


def served(rec: Dict) -> List[Dict]:
    """The served rows the reference scores: the probe's, then those
    sampled before the first update."""
    return rec.get("probe", []) + rec["v0"]


def program_readings(rec: Dict) -> Dict:
    return {"lp": [np.asarray(r["logprobs"], np.float64)
                   for r in served(rec)],
            "losses": list(rec["losses"]),
            "g_first_norms": np.asarray(rec["g_first_norms"], np.float64),
            "dp_norms": np.asarray(rec["dp_norms"], np.float64)}


def reference_readings(c: Dict, mcfg, mix: Dict, seed: int, rec: Dict,
                       first_step: int, width: int, mode: str = "f32",
                       v0: Optional[List[Dict]] = None,
                       batches: Optional[List] = None) -> Dict:
    """The reference's own readings on the recorded tokens and batches
    (``v0`` / ``batches`` replace the recorded served rows and batches,
    for planted faults)."""
    import jax
    from repro.models.model import build_model
    from bench.reference import Reference
    from bench.weights import diff_norms, leaf_names, make_weights

    model = build_model(mcfg)
    ref = Reference(c, mode)
    v0 = served(rec) if v0 is None else v0
    batches = rec["batches"] if batches is None else batches

    def weights():
        return make_weights(model, c, seed)

    p0 = weights()
    names = leaf_names(p0)
    lps = ref.row_logprobs(p0, [r["prompt"] + r["generated"] for r in v0],
                           width)
    lp = [np.asarray(x[len(r["prompt"]):], np.float64)
          for r, x in zip(v0, lps)]
    dtype = jax.tree.leaves(p0)[0].dtype
    out = ref.follow(p0, batches, mix["optimizer"], mix["clip_eps"],
                     first_step, width, dtype)
    del p0
    params = out.pop("params")
    dp = np.asarray(diff_norms(params, weights()), np.float64)
    del params
    return {"lp": lp, "losses": out["losses"],
            "g_first_norms": np.asarray(out["g_first_norms"], np.float64),
            "dp_norms": dp, "names": names}


def _rel_gaps(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Per leaf, |a - b| / max(b, median(b)) (b: the reference)."""
    floor = max(float(np.median(b)), 1e-30)
    return np.abs(a - b) / np.maximum(b, floor)


def leaf_gaps(got: Dict, ref: Dict) -> Dict[str, np.ndarray]:
    """The per-leaf gaps whose worst are ``grad_gap`` and ``update_gap``;
    leaves left out of the change read 0."""
    g_ref = ref["g_first_norms"]
    keep = g_ref >= 1e-3 * np.median(g_ref)
    return {"grad_gap": _rel_gaps(got["g_first_norms"], g_ref),
            "update_gap": np.where(keep, _rel_gaps(got["dp_norms"],
                                                   ref["dp_norms"]), 0.0)}


def gaps(got: Dict, ref: Dict) -> Dict[str, float]:
    lp = max(float(np.max(np.abs(a - b))) for a, b in zip(got["lp"],
                                                         ref["lp"])
             if len(a))
    loss = max(abs(a - b) for a, b in zip(got["losses"], ref["losses"]))
    return {"lp_gap": lp, "loss_gap": float(loss),
            **{k: float(v.max()) for k, v in leaf_gaps(got, ref).items()}}


def compare(c: Dict, mcfg, mix: Dict, seed: int, rec: Dict, first_step: int,
            width: int):
    """The four numbers; and the leaf that sets each of the two read by
    leaf, with the names of the leaves left out of ``update_gap``."""
    got = program_readings(rec)
    ref = reference_readings(c, mcfg, mix, seed, rec, first_step, width)
    leaves = {k: ref["names"][int(np.argmax(v))]
              for k, v in leaf_gaps(got, ref).items()}
    g_ref = ref["g_first_norms"]
    leaves["left_out"] = [n for n, g in zip(ref["names"], g_ref)
                          if g < 1e-3 * np.median(g_ref)]
    return gaps(got, ref), leaves


def load_limits(workload: str) -> Dict[str, float]:
    path = os.path.join(HERE, "limits", f"{workload}.json")
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        return json.load(f)["limits"]


def judge(numbers: Dict[str, float], limits: Optional[Dict]) -> Dict:
    """Each number beside its limit; a number with no limit fails."""
    limits = limits or {}
    return {k: {"value": numbers[k],
                "limit": limits.get(k, float("-inf"))} for k in NUMBERS}
