"""Ragged GQA decode-attention Pallas TPU kernels — the rollout hot layer.

One new token per slot attends over a per-slot-length KV cache.  This is
the kernel the paper's scheduling feeds: length-sorted batches mean
neighbouring slots share similar ``kv_len``, so the kv-block skip pattern
(``@pl.when`` on block start < kv_len) is uniform across the grid and the
engine streams only live cache — the TPU-native payoff of SortedRL's
sorting (see DESIGN.md §3).

Two variants share one kernel body:

* ``ragged_decode_attention`` — dense ``(B, S, Kh, D)`` cache, kv blocks
  addressed contiguously (grid position == block index);
* ``paged_decode_attention`` — the cache is a pool of fixed-size pages
  ``(N, page, Kh, D)`` and each slot owns a *block table* mapping logical
  kv blocks to physical pages (``repro.core.kv_cache``).  The table is a
  scalar-prefetch operand, so the BlockSpec index_map dereferences it to
  DMA exactly the pages a slot maps — shared GRPO prefix pages stream
  once per slot without ever materialising a dense per-slot cache.

Tiling: grid (B, S // block_k); each program holds the full (H, D) query
tile in VMEM plus one (block_k, Kh, D) cache tile; flash-decode online
softmax accumulates in VMEM scratch across the sequential k dimension.
Per-slot lengths (and the paged variant's block tables) are
scalar-prefetch operands in SMEM.  D is the lane dimension; for the paged
variant block_k == page size (16 and 128 both compile for TPU v5e).
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


def _flash_decode_block(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                        *, kblk, nk, kstart, kv_len, softcap: float,
                        k_scale=None, v_scale=None):
    """Shared flash-decode body: one (block_k, Kh, D) kv tile starting at
    logical position `kstart`, online-softmax accumulated in VMEM scratch.
    Refs: q (H, D) | k/v (block_k, Kh, D) | o (H, D) |
    scratch m/l (H, 1) f32, acc (H, D) f32.
    ``k_scale``/``v_scale``: per-page dequant scalars (int8 KV pages)."""

    @pl.when(kblk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kstart < kv_len)           # ragged block skip
    def _compute():
        q = q_ref[...].astype(jnp.float32)            # (H, D)
        k = k_ref[...].astype(jnp.float32)            # (bk, Kh, D)
        v = v_ref[...].astype(jnp.float32)
        if k_scale is not None:
            k = k * k_scale
        if v_scale is not None:
            v = v * v_scale
        H, D = q.shape
        bk, Kh, _ = k.shape
        G = H // Kh
        qg = q.reshape(Kh, G, D) / math.sqrt(D)
        s = jnp.einsum("hgd,khd->hgk", qg, k,
                       preferred_element_type=jnp.float32)   # (Kh, G, bk)
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        pos = kstart + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
        s = jnp.where(pos < kv_len, s, NEG_INF)
        m_prev = m_ref[...].reshape(Kh, G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(pos < kv_len, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                      # (Kh, G, 1)
        l_new = l_ref[...].reshape(Kh, G, 1) * alpha + jnp.sum(
            p, axis=-1, keepdims=True)
        pv = jnp.einsum("hgk,khd->hgd", p, v,
                        preferred_element_type=jnp.float32)
        acc_ref[...] = (acc_ref[...].reshape(Kh, G, D) * alpha
                        + pv).reshape(H, D)
        m_ref[...] = m_new.reshape(H, 1)
        l_ref[...] = l_new.reshape(H, 1)

    @pl.when(kblk == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _kernel(kv_len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block_k: int, softcap: float):
    """Dense variant: kv block `kb` sits at cache rows [kb*block_k, ...)."""
    b = pl.program_id(0)
    kblk = pl.program_id(1)
    _flash_decode_block(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                        kblk=kblk, nk=pl.num_programs(1),
                        kstart=kblk * block_k, kv_len=kv_len_ref[b],
                        softcap=softcap)


def _paged_kernel(bt_ref, kv_len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size: int, softcap: float):
    """Paged variant: the BlockSpec index_map already dereferenced the
    block table (scalar prefetch), so k/v refs hold the physical page for
    logical block `kb`; only the position base differs from dense."""
    del bt_ref   # consumed by the index_map
    b = pl.program_id(0)
    kblk = pl.program_id(1)
    _flash_decode_block(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                        kblk=kblk, nk=pl.num_programs(1),
                        kstart=kblk * page_size, kv_len=kv_len_ref[b],
                        softcap=softcap)


def _paged_kernel_int8(bt_ref, kv_len_ref, ks_ref, vs_ref, q_ref, k_ref,
                       v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                       page_size: int, softcap: float):
    """int8-page variant: k/v pages are stored quantized with one f32
    scale per physical page; the scales ride in as scalar-prefetch
    operands and are dereferenced through the same block table as the
    page itself, so dequant happens in-register after the page DMA."""
    b = pl.program_id(0)
    kblk = pl.program_id(1)
    page = bt_ref[b, kblk]
    _flash_decode_block(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                        kblk=kblk, nk=pl.num_programs(1),
                        kstart=kblk * page_size, kv_len=kv_len_ref[b],
                        softcap=softcap,
                        k_scale=ks_ref[page], v_scale=vs_ref[page])


def ragged_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                            v_cache: jnp.ndarray, kv_len: jnp.ndarray,
                            *, block_k: int = 128, softcap: float = 0.0,
                            interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, H, D); k/v_cache: (B, S, Kh, D); kv_len: (B,) -> (B, H, D).

    ``interpret=None`` interprets only on the CPU backend
    (:func:`repro.kernels.default_interpret`).
    """
    B, H, D = q.shape
    S, Kh = k_cache.shape[1], k_cache.shape[2]
    assert S % block_k == 0, (S, block_k)
    kernel = functools.partial(_kernel, block_k=block_k, softcap=softcap)

    def q_map(b, kb, kl):
        return (b, 0, 0)

    def kv_map(b, kb, kl):
        return (b, kb, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,       # kv_len
        grid=(B, S // block_k),
        in_specs=[
            pl.BlockSpec((None, H, D), q_map),
            pl.BlockSpec((None, block_k, Kh, D), kv_map),
            pl.BlockSpec((None, block_k, Kh, D), kv_map),
        ],
        out_specs=pl.BlockSpec((None, H, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=default_interpret() if interpret is None else interpret,
        name="ragged_decode_attention",
    )(kv_len.astype(jnp.int32), q, k_cache, v_cache)


def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                           kv_len: jnp.ndarray, *, softcap: float = 0.0,
                           k_scales: jnp.ndarray = None,
                           v_scales: jnp.ndarray = None,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Decode attention over a paged KV pool.

    q: (B, H, D); k/v_pages: (N, page, Kh, D) physical page pool;
    block_tables: (B, nb) i32 — logical kv block j of slot b lives in
    physical page ``block_tables[b, j]`` (pad unused entries with any
    valid page id; rows past ``kv_len`` are masked); kv_len: (B,) valid
    lengths.  Returns (B, H, D).

    The block table and kv_len ride in as scalar-prefetch operands so the
    k/v index_maps can dereference the table — each grid step DMAs one
    physical page, which is how a GRPO group's shared prefix pages are
    read by every member without a dense per-slot copy.

    ``k_scales``/``v_scales``: (N,) f32 per-page dequant scales for int8
    page pools (``kv_quant="int8"`` engines).  They join the scalar
    prefetch so the kernel dequantises each page in-register right after
    its DMA — the pool stays int8 in HBM, halving (vs bf16; quartering vs
    f32) the decode's memory traffic and doubling effective capacity.

    ``interpret=None`` interprets only on the CPU backend.
    """
    B, H, D = q.shape
    page, Kh = k_pages.shape[1], k_pages.shape[2]
    nb = block_tables.shape[1]
    assert block_tables.shape[0] == B and kv_len.shape == (B,)
    quant = k_scales is not None
    assert quant == (v_scales is not None)
    if quant:
        kernel = functools.partial(_paged_kernel_int8, page_size=page,
                                   softcap=softcap)
        nsp = 4                      # block_tables, kv_len, k/v_scales
        scalar_ops = (block_tables.astype(jnp.int32),
                      kv_len.astype(jnp.int32),
                      k_scales.astype(jnp.float32),
                      v_scales.astype(jnp.float32))

        def q_map(b, kb, bt, kl, ks, vs):
            return (b, 0, 0)

        def kv_map(b, kb, bt, kl, ks, vs):
            return (bt[b, kb], 0, 0, 0)
    else:
        kernel = functools.partial(_paged_kernel, page_size=page,
                                   softcap=softcap)
        nsp = 2                      # block_tables, kv_len
        scalar_ops = (block_tables.astype(jnp.int32),
                      kv_len.astype(jnp.int32))

        def q_map(b, kb, bt, kl):
            return (b, 0, 0)

        def kv_map(b, kb, bt, kl):
            return (bt[b, kb], 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((None, H, D), q_map),
            pl.BlockSpec((None, page, Kh, D), kv_map),
            pl.BlockSpec((None, page, Kh, D), kv_map),
        ],
        out_specs=pl.BlockSpec((None, H, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=default_interpret() if interpret is None else interpret,
        name="paged_decode_attention",
    )(*scalar_ops, q, k_pages, v_pages)


# ---------------------------------------------------------------------------
# Fused sampling: LM head matmul + greedy token + logsumexp in one pass
# ---------------------------------------------------------------------------

def _fused_sample_kernel(x_ref, w_ref, val_ref, idx_ref, lse_ref, l_ref, *,
                         block_v: int, vocab: int, softcap: float):
    """Every (Bp, Dm) hidden row x one (Dm, block_v) head slice per
    program, so the head streams through VMEM once per call.  The outputs
    stay resident across the sequential vocab axis and carry the running
    max logit (val) and its index (idx); l_ref carries the running sum of
    exp(logit - max).  A block's max replaces the running one only when
    strictly greater, and inside a block the lowest index at the max
    wins, which is argmax's first-occurrence rule."""
    vblk = pl.program_id(0)

    @pl.when(vblk == 0)
    def _init():
        val_ref[...] = jnp.full_like(val_ref, NEG_INF)
        idx_ref[...] = jnp.zeros_like(idx_ref)
        l_ref[...] = jnp.zeros_like(l_ref)

    x = x_ref[...].astype(jnp.float32)                    # (Bp, Dm)
    w = w_ref[...].astype(jnp.float32)                    # (Dm, bv)
    s = jnp.dot(x, w, preferred_element_type=jnp.float32)  # (Bp, bv)
    if softcap > 0:
        s = jnp.tanh(s / softcap) * softcap
    col = vblk * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < vocab, s, NEG_INF)                # head padding
    b_max = jnp.max(s, axis=-1, keepdims=True)            # (Bp, 1)
    b_idx = jnp.min(jnp.where(s == b_max, col, vocab), axis=-1,
                    keepdims=True)
    m_prev = val_ref[...]
    m_new = jnp.maximum(m_prev, b_max)
    p = jnp.where(col < vocab, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(
        p, axis=-1, keepdims=True)
    idx_ref[...] = jnp.where(b_max > m_prev, b_idx, idx_ref[...])
    val_ref[...] = m_new

    @pl.when(vblk == pl.num_programs(0) - 1)
    def _finalize():
        lse_ref[...] = val_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))


def fused_sample(x: jnp.ndarray, w: jnp.ndarray, *, block_v: int = 128,
                 softcap: float = 0.0, interpret: Optional[bool] = None):
    """Fused LM-head + greedy sampling epilogue for the paged decode step.

    x: (B, Dm) final-normed hidden states; w: (Dm, V) head weights.
    Returns (val (B, 1) f32, idx (B, 1) i32, lse (B, 1) f32): the largest
    (softcapped) logit, its first vocab index, and the logsumexp over the
    full vocab — the greedy token is idx[:, 0] and its logprob
    val[:, 0] - lse[:, 0] — without materialising the (B, V) logits.
    Rows are padded to a multiple of 8 (the sublane tile).
    ``interpret=None`` interprets only on the CPU backend.
    """
    B, Dm = x.shape
    V = w.shape[1]
    assert w.shape[0] == Dm, (x.shape, w.shape)
    nv = -(-V // block_v)
    if V % block_v:
        w = jnp.pad(w, ((0, 0), (0, nv * block_v - V)))
    Bp = -(-B // 8) * 8
    if Bp != B:
        x = jnp.pad(x, ((0, Bp - B), (0, 0)))
    kernel = functools.partial(_fused_sample_kernel, block_v=block_v,
                               vocab=V, softcap=softcap)
    row = pl.BlockSpec((Bp, 1), lambda vb: (0, 0))
    val, idx, lse = pl.pallas_call(
        kernel,
        grid=(nv,),
        in_specs=[
            pl.BlockSpec((Bp, Dm), lambda vb: (0, 0)),
            pl.BlockSpec((Dm, block_v), lambda vb: (0, vb)),
        ],
        out_specs=[row, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Bp, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((Bp, 1), jnp.float32)],
        interpret=default_interpret() if interpret is None else interpret,
        name="fused_sample",
    )(x, w)
    return val[:B], idx[:B], lse[:B]
