"""Plain reference: a configuration's decoder forward in float32
jax.numpy, from its model family (``bench/families/<family>.py``), with
what no family changes: token logprobs, the GRPO advantage, the clipped
policy-gradient loss (paper Eq. 1 with DAPO's clip-higher) and AdamW,
written from their descriptions and importing nothing of the program.

The forward reads the weight tree of :mod:`bench.weights` by leaf name
(``embed``, ``layers/attn/wq`` ...), with the program's orientation of each
matrix, and multiplies through :func:`mm`.  ``mode="f32"`` multiplies at
full float32 precision; ``mode="fp8"`` rounds both operands of every matrix
product to float8 (e4m3) first (gradients pass the rounding straight
through) and is the control: the reference computed one precision below
the bfloat16 that the configurations state.

Rows are processed one at a time, and layers under ``jax.checkpoint``, so
that a 1024-token row of a 0.6B model fits beside float32 AdamW state.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import family
from bench.weights import leaf_norms

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(x):
    """x rounded to float8 e4m3 in the forward pass; the gradient passes
    through unrounded (straight through), so the backward pass is not
    flushed to e4m3's range."""
    x = x.astype(F32)
    return x + jax.lax.stop_gradient(
        x.astype(jnp.float8_e4m3fn).astype(F32) - x)


def mm(eq: str, a, b, mode: str):
    """``jnp.einsum`` at full float32 precision; under the control
    (``mode="fp8"``) both operands are rounded to e4m3 first.  Every
    family's forward multiplies through here."""
    if mode == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(eq, a.astype(F32), b.astype(F32), precision=HIGHEST)


class Reference:
    """``c`` is the configuration file's dict (published keys)."""

    def __init__(self, c: Dict, mode: str = "f32"):
        assert mode in ("f32", "fp8"), mode
        self.forward = family.load(c).Forward(c, mode)

    def logits(self, params, tokens):
        """tokens (B, S) -> float32 logits (B, S, V)."""
        return self.forward.logits(params, tokens)

    def token_logprobs(self, params, tokens):
        """(B, S): entry t is log p(tokens[t] | tokens[:t]); entry 0 is 0."""
        lg = self.logits(params, tokens)[:, :-1]
        lse = jax.nn.logsumexp(lg, axis=-1)
        tok = jnp.take_along_axis(lg, tokens[:, 1:, None], axis=-1)[..., 0]
        return jnp.pad(tok - lse, ((0, 0), (1, 0)))

    # -- training ------------------------------------------------------------

    @functools.partial(jax.jit, static_argnums=(0,))
    def _row_lp(self, params, tokens):
        return self.token_logprobs(params, tokens)

    def row_logprobs(self, params, rows: Sequence[Sequence[int]],
                     width: int) -> List[np.ndarray]:
        """Token logprobs of each row, one row per call, padded to the
        next power of two (at most ``width``); padding after a row does
        not reach it under the causal mask."""
        out = []
        for r in rows:
            t = np.zeros((1, min(1 << (len(r) - 1).bit_length(), width)),
                         np.int32)
            t[0, :len(r)] = r
            out.append(np.asarray(self._row_lp(params, jnp.asarray(t)))[0,
                                                                        :len(r)])
        return out

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    def _accumulate(self, gacc, params, tokens, mask, adv, old_lp, clip_lo,
                    clip_hi, n_tokens):
        def objective(p):
            lp = self.token_logprobs(p, tokens)
            ratio = jnp.exp(lp - old_lp)
            obj = jnp.minimum(ratio * adv,
                              jnp.clip(ratio, 1 - clip_lo, 1 + clip_hi) * adv)
            return -jnp.sum(obj * mask) / n_tokens

        loss, g = jax.value_and_grad(objective)(params)
        return jax.tree.map(jnp.add, gacc, g), loss

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2, 3))
    def _adamw(self, params, m, v, grads, t, lr, b1, b2, eps, wd, clip,
               store_dtype_probe):
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.where(gnorm > clip, clip / gnorm, 1.0)
        grads = jax.tree.map(lambda g: g * scale, grads)
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t

        def upd(p, m, v):
            new = p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p)
            # parameters are stored in the configuration's dtype
            return new.astype(store_dtype_probe.dtype).astype(F32)

        return jax.tree.map(upd, params, m, v), m, v, grads

    def follow(self, params0, batches: List[Dict], opt: Dict,
               clip_eps: Sequence[float], first_step: int, width: int,
               store_dtype) -> Dict:
        """Take the program's first updates on the same batches from the
        same weights.  ``batches``: per update, rows of dicts with
        ``prompt``, ``generated``, ``logprobs``, ``reward``, ``group``.
        ``first_step``: the optimizer's step count before the first of
        them.  Returns each update's loss, the :func:`leaf_norms` of the
        first update's clipped gradient and the parameters (float32) after
        the last update."""
        params = jax.tree.map(lambda x: x.astype(F32), params0)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        probe = jnp.zeros((), store_dtype)
        losses, g_first = [], None
        for i, rows in enumerate(batches):
            adv = grpo_advantages([r["reward"] for r in rows],
                                  [r["group"] for r in rows])
            n_tok = float(sum(len(r["generated"]) for r in rows))
            gacc = jax.tree.map(jnp.zeros_like, params)
            loss = 0.0
            for r, a in zip(rows, adv):
                t, mask, adv_t, old = row_arrays(r, a, width)
                gacc, lo = self._accumulate(gacc, params, t, mask, adv_t, old,
                                            clip_eps[0], clip_eps[1], n_tok)
                loss += float(lo)
            losses.append(loss)
            params, m, v, g = self._adamw(
                params, m, v, gacc, float(first_step + i + 1), opt["lr"],
                opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
                opt["grad_clip"], probe)
            if i == 0:
                g_first = np.asarray(leaf_norms(g))
            del gacc, g
        del m, v
        return {"losses": losses, "g_first_norms": g_first, "params": params}


def grpo_advantages(rewards: Sequence[float], groups: Sequence) -> List[float]:
    """(r - mean) / (std + 1e-6) over the rows of the same prompt in the
    update batch (population std); a row alone in its group gets 0."""
    out = []
    for r, g in zip(rewards, groups):
        mates = [rr for rr, gg in zip(rewards, groups) if gg == g]
        mu = sum(mates) / len(mates)
        sd = math.sqrt(sum((x - mu) ** 2 for x in mates) / len(mates))
        out.append((r - mu) / (sd + 1e-6))
    return out


def row_arrays(r: Dict, adv: float, width: int):
    n_p, n_g = len(r["prompt"]), len(r["generated"])
    t = np.zeros((1, width), np.int32)
    t[0, :n_p + n_g] = list(r["prompt"]) + list(r["generated"])
    mask = np.zeros((1, width), np.float32)
    mask[0, n_p:n_p + n_g] = 1.0
    old = np.zeros((1, width), np.float32)
    old[0, n_p:n_p + n_g] = r["logprobs"]
    return (jnp.asarray(t), jnp.asarray(mask), jnp.asarray(mask * adv),
            jnp.asarray(old))
