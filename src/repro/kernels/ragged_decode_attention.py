"""Ragged GQA decode-attention Pallas TPU kernels — the rollout hot layer.

One new token per slot attends over a per-slot-length KV cache.  This is
the kernel the paper's scheduling feeds: length-sorted batches mean
neighbouring slots share similar ``kv_len``, so the engine streams only
live cache — the TPU-native payoff of SortedRL's sorting.

* ``ragged_decode_attention`` — dense ``(B, S, Kh, D)`` cache; grid
  (B, S // block_k), one (block_k, Kh, D) tile per program, blocks past
  ``kv_len`` skipped with ``@pl.when``.
* ``paged_decode_attention`` — the decode step's attention on the
  engine's layered page pool ``(L, N, page, Kh, D)``, read in place: the
  layer index, the block tables and a work list of live blocks are
  scalar-prefetch operands, and the kernel DMAs each live page of the
  layer straight from HBM, several pages per block and double-buffered
  across blocks and slots.  No dense per-slot view exists; shared GRPO
  prefix pages are read by every member that maps them.  The step's new
  token is not in the pool yet: it joins the kernel's online-softmax
  partials afterwards (``_with_new_token``), and the engine writes its
  row into the pool after the layer scan.

D is the lane dimension; Kh * D rows of a page are the sublanes.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import default_interpret

NEG_INF = -1e30


def _flash_decode_block(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                        *, kblk, nk, kstart, kv_len, softcap: float):
    """Shared flash-decode body: one (block_k, Kh, D) kv tile starting at
    logical position `kstart`, online-softmax accumulated in VMEM scratch.
    Refs: q (H, D) | k/v (block_k, Kh, D) | o (H, D) |
    scratch m/l (H, 1) f32, acc (H, D) f32."""

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
    """kv block `kb` sits at cache rows [kb*block_k, ...)."""
    b = pl.program_id(0)
    kblk = pl.program_id(1)
    _flash_decode_block(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                        kblk=kblk, nk=pl.num_programs(1),
                        kstart=kblk * block_k, kv_len=kv_len_ref[b],
                        softcap=softcap)


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


def _softcap(s, softcap: float):
    return jnp.tanh(s / softcap) * softcap if softcap > 0 else s


PAGES_PER_BLOCK = 16    # 256 tokens a block at the engine's 16-token pages


def _paged_kernel(layer_ref, bt_ref, kv_len_ref, slot_ref, blk_ref,
                  count_ref, *refs, ppb: int, nbp: int, quant: bool,
                  softcap: float):
    """Flash-decode partials over the live blocks of one layer's pages.

    Scalar prefetch: the layer, the padded block table flattened
    (B * nbp,), kv_len (B,), the work list of (slot, block) pairs, its
    length, and for int8 pools each (slot, page) scale of this layer.
    Work item t covers logical pages [blk*ppb, (blk+1)*ppb) of its slot;
    a page wholly past kv_len is not copied.  While item t is computed the
    pages of item t+1 are in flight into the other buffer, so the copies
    of one slot's last block and the next slot's first overlap too.

    Rows of a block are (token, kv head) pairs: the scores of every query
    head against every row come out of one matmul and the rows of other
    kv heads are masked (``head_ref``), which keeps the kernel to 2-D
    matmuls on the (rows, D) tile at any GQA ratio.  The outputs hold
    each slot's running max m, sum l and unnormalised acc (B, H, ...)."""
    if quant:
        ks_ref, vs_ref, *refs = refs
    (q_ref, head_ref, tok_ref, k_hbm, v_hbm, m_ref, l_ref, acc_ref,
     kbuf, vbuf, sems) = refs
    _, page, kh, d = kbuf.shape[1:]
    rows, bk = ppb * page * kh, ppb * page
    layer, count = layer_ref[0], count_ref[0]
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    # pages left uncopied must hold finite values (their p is 0)
    kbuf[...] = jnp.zeros_like(kbuf)
    vbuf[...] = jnp.zeros_like(vbuf)

    def page_copies(t, buf):
        b, i = slot_ref[t], blk_ref[t]
        for j in range(ppb):
            p = bt_ref[b * nbp + i * ppb + j]
            yield ((i * ppb + j) * page < kv_len_ref[b],
                   pltpu.make_async_copy(k_hbm.at[layer, p], kbuf.at[buf, j],
                                         sems.at[0, buf]),
                   pltpu.make_async_copy(v_hbm.at[layer, p], vbuf.at[buf, j],
                                         sems.at[1, buf]))

    def start(t, buf):
        for live, ck, cv in page_copies(t, buf):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(t, buf):
        for live, ck, cv in page_copies(t, buf):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    def load(buf_ref, sc_ref, buf, col):
        if sc_ref is None:
            x = buf_ref[buf].astype(jnp.float32).reshape(rows, d)
        else:
            x = jnp.concatenate(
                [(buf_ref[buf, j].astype(jnp.float32)
                  * sc_ref[col + j]).reshape(page * kh, d)
                 for j in range(ppb)], axis=0)
        return x.astype(q_ref.dtype)

    @pl.when(count > 0)
    def _():
        start(0, 0)

    def body(t, carry):
        buf = t % 2

        @pl.when(t + 1 < count)
        def _():
            start(t + 1, 1 - buf)

        wait(t, buf)
        b, i = slot_ref[t], blk_ref[t]
        col = b * nbp + i * ppb
        k = load(kbuf, ks_ref if quant else None, buf, col)   # (rows, D)
        v = load(vbuf, vs_ref if quant else None, buf, col)
        s = _softcap(jax.lax.dot_general(
            q_ref[b], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), softcap)
        valid = (head_ref[...] > 0) & (i * bk + tok_ref[...] < kv_len_ref[b])
        s = jnp.where(valid, s, NEG_INF)                      # (H, rows)
        m_prev = m_ref[b]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[b] = l_ref[b] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[b] = acc_ref[b] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[b] = m_new
        return carry

    jax.lax.fori_loop(0, count, body, 0)


def _with_new_token(qs, m, l, acc, k_new, v_new, softcap: float):
    """Fold the new token's own key and value into the pool partials:
    the merge of two online-softmax states, the second of one row."""
    B, H, D = qs.shape
    Kh = k_new.shape[1]
    G = H // Kh
    s = jnp.einsum("bkgd,bkd->bkg", qs.reshape(B, Kh, G, D),
                   k_new.astype(qs.dtype),
                   preferred_element_type=jnp.float32).reshape(B, H)
    s = _softcap(s, softcap)
    m, l = m[..., 0], l[..., 0]
    top = jnp.maximum(m, s)
    a, c = jnp.exp(m - top), jnp.exp(s - top)
    vn = jnp.repeat(v_new.astype(jnp.float32), G, axis=1)
    return ((acc * a[..., None] + c[..., None] * vn)
            / (l * a + c)[..., None])


def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                           kv_len: jnp.ndarray, layer, k_new: jnp.ndarray,
                           v_new: jnp.ndarray, *, softcap: float = 0.0,
                           k_scales: Optional[jnp.ndarray] = None,
                           v_scales: Optional[jnp.ndarray] = None,
                           interpret: Optional[bool] = None) -> jnp.ndarray:
    """One decode step's attention for one layer, on the page pool in place.

    q: (B, H, D); k/v_pages: (L, N, page, Kh, D) the layered pool;
    block_tables: (B, nb) i32, logical page j of slot b in physical page
    ``block_tables[b, j]`` (padding: any valid page, the engine's garbage
    page); kv_len: (B,) rows already in the pool; layer: scalar index into
    the pool's first axis; k_new/v_new: (B, Kh, D) the new token's key and
    value, at position kv_len.  Returns (B, H, D): attention over rows
    [0, kv_len) of the layer's pages plus the new token.  A slot with
    kv_len 0 (an inactive one) attends to its new token alone.

    ``k_scales``/``v_scales``: (L, N) f32 per-(layer, page) dequant scales
    of an int8 pool; this layer's scale of each mapped page rides in
    scalar memory and dequantizes the page in VMEM after its copy.  The
    matmuls run in the dtype of ``k_new`` with f32 accumulation.

    The pool is handed whole (``pl.ANY``) with the layer index, so a call
    inside the model's layer scan slices nothing out of it.
    ``interpret=None`` interprets only on the CPU backend.
    """
    B, H, D = q.shape
    _, _, page, Kh, _ = k_pages.shape
    nb = block_tables.shape[1]
    assert block_tables.shape[0] == B and kv_len.shape == (B,)
    quant = k_scales is not None
    assert quant == (v_scales is not None)
    ppb = min(PAGES_PER_BLOCK, nb)
    nbp = -(-nb // ppb) * ppb
    nblk_max, rows, bk = nbp // ppb, ppb * page * Kh, ppb * page
    cdt = k_new.dtype
    bt = jnp.pad(block_tables.astype(jnp.int32), ((0, 0), (0, nbp - nb)))
    kv_len = kv_len.astype(jnp.int32)
    # the work list: every live block of every slot, slot-major
    nblk = (kv_len + bk - 1) // bk
    ends = jnp.cumsum(nblk)
    t = jnp.arange(B * nblk_max, dtype=jnp.int32)
    slot = jnp.minimum(jnp.sum(ends[None, :] <= t[:, None], axis=1),
                       B - 1).astype(jnp.int32)
    blk = (t - (ends - nblk)[slot]).astype(jnp.int32)
    scalars = [jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
               bt.reshape(-1), kv_len, slot, blk, ends[-1:]]
    if quant:
        scalars += [jnp.take(sc[layer], bt.reshape(-1)).astype(jnp.float32)
                    for sc in (k_scales, v_scales)]
    # row r of a block is (token r // Kh, kv head r % Kh)
    r = np.arange(rows)
    head_ok = jnp.asarray((r[None, :] % Kh) == (np.arange(H)[:, None]
                                                // (H // Kh)), jnp.int32)
    tok = jnp.asarray((r // Kh)[None, :], jnp.int32)
    qs = (q.astype(jnp.float32) / math.sqrt(D)).astype(cdt)

    def whole(shape):
        return pl.BlockSpec(shape, lambda g, *_: (0,) * len(shape))

    kernel = functools.partial(_paged_kernel, ppb=ppb, nbp=nbp, quant=quant,
                               softcap=softcap)
    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(1,),
            in_specs=[whole((B, H, D)), whole((H, rows)), whole((1, rows)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[whole((B, H, 1)), whole((B, H, 1)),
                       whole((B, H, D))],
            scratch_shapes=[
                pltpu.VMEM((2, ppb, page, Kh, D), k_pages.dtype),
                pltpu.VMEM((2, ppb, page, Kh, D), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, D), jnp.float32)],
        interpret=default_interpret() if interpret is None else interpret,
        name="paged_decode_attention",
    )(*scalars, qs, head_ok, tok, k_pages, v_pages)
    return _with_new_token(qs, m, l, acc, k_new, v_new,
                           softcap).astype(q.dtype)


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
