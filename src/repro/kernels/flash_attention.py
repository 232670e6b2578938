"""Causal GQA flash-attention Pallas TPU kernel (prefill path).

Partial-mode resume re-prefills scavenged prefixes (paper §3.2), so prefill
throughput is on the rollout critical path alongside decode.  Blockwise
online softmax with causal *and* sliding-window block skipping: a kv block
is visited only when it intersects [q_start - window, q_end] — local
(gemma2) layers touch O(S * W) instead of O(S^2) work.

Tiling: grid (B, H, S//block_q, S//block_k) over a head-major layout
(q/k/v transposed to (B, H, S, D)), so each (block_q, D) query tile and
(block_k, D) kv tile is a whole (8, 128)-aligned slab; f32 scratch
accumulators.  GQA maps query head h to kv head h // (H // Kh) in the
BlockSpec index map.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, *rest, block_q: int, block_k: int,
            window: int, softcap: float, scale: float, seg: bool):
    if seg:
        sq_ref, sk_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        sq_ref = sk_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    qblk = pl.program_id(2)
    kblk = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kblk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qblk * block_q
    k_start = kblk * block_k
    # causal skip: kv block entirely after the q block
    visible = k_start <= q_start + block_q - 1
    if window > 0:
        # window skip: kv block entirely before the window of every q row
        visible = jnp.logical_and(
            visible, k_start + block_k - 1 >= q_start - window + 1)

    @pl.when(visible)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[...].astype(jnp.float32)                  # (bk, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = qpos >= kpos
        if window > 0:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        if seg:
            # packed prefill: no attention across segment boundaries
            # (block_q, 1) query ids against (1, block_k) key ids
            mask = jnp.logical_and(mask, sq_ref[...] == sk_ref[...])
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                 # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kblk == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    seg_ids: jnp.ndarray = None,
                    block_q: int = 128, block_k: int = 128,
                    window: int = 0, softcap: float = 0.0,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, S, H, D); k/v: (B, S, Kh, D) -> (B, S, H, D).  Causal.

    ``seg_ids``: optional (B, S) int32 segment ids for packed ragged
    prefill — several prompts concatenated per row attend only within
    their own segment (pad positions carry -1; their output rows are
    garbage and must be discarded by the caller).  The ids ride in twice,
    as a (B, S, 1) column for queries and a (B, 1, S) row for keys, so
    both tiles keep the array's full extent in the squeezed dimension.
    ``interpret=None`` interprets only on the CPU backend."""
    B, S, H, D = q.shape
    Kh = k.shape[2]
    G = H // Kh
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    grid = (B, H, S // block_q, S // block_k)
    kernel = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                               window=window, softcap=softcap,
                               scale=1.0 / math.sqrt(D),
                               seg=seg_ids is not None)
    q_spec = pl.BlockSpec((None, None, block_q, D),
                          lambda b, h, qb, kb: (b, h, qb, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, D),
                           lambda b, h, qb, kb: (b, h // G, kb, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    inputs = [jnp.swapaxes(x, 1, 2) for x in (q, k, v)]   # head-major
    if seg_ids is not None:
        assert seg_ids.shape == (B, S), (seg_ids.shape, (B, S))
        seg = seg_ids.astype(jnp.int32)
        in_specs.append(pl.BlockSpec((None, block_q, 1),
                                     lambda b, h, qb, kb: (b, qb, 0)))
        in_specs.append(pl.BlockSpec((None, 1, block_k),
                                     lambda b, h, qb, kb: (b, 0, kb)))
        inputs.extend([seg[:, :, None], seg[:, None, :]])
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=default_interpret() if interpret is None else interpret,
        name="flash_attention",
    )(*inputs)
    return jnp.swapaxes(out, 1, 2)
