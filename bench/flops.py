"""Operations the window's work requires, from shapes alone, on the counts
of the configuration's model family (``bench/families/<family>.py``).

Training counts forward + backward as 3 forwards, with no recomputation
(remat is the program's choice, not required work).
"""
from __future__ import annotations

from types import SimpleNamespace
from typing import Dict, Iterable, Tuple

from bench import family

# what a family module counts (bench/families/__init__.py)
FAMILY_COUNTS = ("forward_flops", "decode_step_least")


def causal_ctx(n: float) -> float:
    """Keys read by a causal pass over n tokens: 1 + 2 + ... + n."""
    return n * (n + 1) / 2.0


def update_flops(c: Dict, rows: Iterable[Tuple[int, int]]) -> float:
    """Forward + backward over the real (unpadded) tokens of update rows
    given as (prompt length, generated length)."""
    forward_flops = family.load(c).forward_flops
    tot = 0.0
    for p, g in rows:
        T = p + g
        tot += 3.0 * forward_flops(c, T, causal_ctx(T))
    return tot


def window_flops(c: Dict, w: Dict) -> float:
    """FLOPs the window required: decode and prefill forwards (a shared
    prefix once) and the updates' forward + backward on real tokens.
    ``w``: decode_tokens, decode_ctx, prefill_tokens, prefill_ctx,
    update_rows."""
    forward_flops = family.load(c).forward_flops
    return (forward_flops(c, w["decode_tokens"], w["decode_ctx"])
            + forward_flops(c, w["prefill_tokens"], w["prefill_ctx"])
            + update_flops(c, w["update_rows"]))


def counts(c: Dict) -> SimpleNamespace:
    """The counts the metric readers use (``ctx["flops"]``): the family's
    own, and the update's and the window's built on them; each takes the
    configuration first."""
    fam = family.load(c)
    return SimpleNamespace(**{k: getattr(fam, k) for k in FAMILY_COUNTS},
                           causal_ctx=causal_ctx, update_flops=update_flops,
                           window_flops=window_flops)
