"""Pure-jnp oracles for every Pallas kernel and for the engine's decode
program (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L

# Documented numeric tolerance of int8 KV pages: max-abs-error of the
# paged decode attention OUTPUT (and hence, through the LM head, of the
# decode logits up to the head's Lipschitz constant) versus the fp path
# on the same KV, for per-page symmetric scale quantization
# (scale = amax / 127).  Asserted by the kernel oracle tests
# (tests/test_kernels.py) and documented in README §Kernel & memory
# roofline.
KV_INT8_DECODE_ATOL = 0.05


def ragged_decode_attention_ref(q, k_cache, v_cache, kv_len,
                                softcap: float = 0.0) -> jnp.ndarray:
    """(B, H, D) x (B, S, Kh, D) x (B,) -> (B, H, D)."""
    return L.decode_attention(q, k_cache, v_cache, kv_len, softcap=softcap)


def gather_pages(pages: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Materialise a dense per-slot cache view from a paged pool.

    pages: (N, page, Kh, D); block_tables: (B, nb) -> (B, nb*page, Kh, D).
    """
    B, nb = block_tables.shape
    g = jnp.take(pages, block_tables.reshape(-1), axis=0)
    return g.reshape(B, nb * pages.shape[1], *pages.shape[2:])


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, kv_len,
                               layer, k_new, v_new, softcap: float = 0.0,
                               k_scales=None, v_scales=None) -> jnp.ndarray:
    """Oracle of ``paged_decode_attention``, and the served path where the
    kernel could only be interpreted: gather the layer's pages of each
    slot, dequantize (int8 pools, per-(layer, page) scales) to the new
    token's dtype, write the new token at kv_len and attend over
    kv_len + 1 rows with f32 accumulation.

    (B, H, D) x (L, N, page, Kh, D) pools x (B, nb) x (B,) x layer x
    (B, Kh, D) new rows -> (B, H, D)."""
    B = q.shape[0]

    def view(pages, scales, new):
        g = pages[layer][block_tables]                  # (B, nb, P, Kh, D)
        if scales is not None:
            s = scales[layer][block_tables]
            g = g.astype(jnp.float32) * s[..., None, None, None]
        g = g.reshape(B, -1, *g.shape[3:]).astype(new.dtype)
        return g.at[jnp.arange(B), kv_len].set(new)

    return L.decode_attention(q, view(k_pages, k_scales, k_new),
                              view(v_pages, v_scales, v_new), kv_len + 1,
                              softcap=softcap)


def paged_decode_step_ref(model, params, token, cache, scales, block_tables,
                          kv_len, return_hidden: bool = False):
    """Oracle of the engine's decode program: one step over the page pool
    by way of a gathered dense view.  Every slot's pages are gathered into
    (L, B, nb*page, Kh, D), the model's dense ``decode_step`` runs on the
    view, and each slot's page holding the new token goes back to its
    physical page; an int8 pool dequantizes on the gather and requantizes
    the written page with a monotone-nondecreasing scale, so a page whose
    amax did not grow keeps its old cells exactly.  Inactive slots write
    the garbage page.  Returns (logits or final-normed hidden, cache,
    scales)."""
    P = cache["k"].shape[2]
    B, nb = block_tables.shape
    cdt = model.cfg.compute_dtype
    quant = bool(scales)

    def gather(pages, sc):
        g = jnp.take(pages, block_tables.reshape(-1), axis=1)
        g = g.reshape(pages.shape[0], B, nb, P, *pages.shape[3:])
        if quant:
            s = jnp.take(sc, block_tables.reshape(-1), axis=1)
            g = (g.astype(jnp.float32) * s.reshape(pages.shape[0], B, nb)[
                ..., None, None, None]).astype(cdt)
        return g.reshape(pages.shape[0], B, nb * P, *pages.shape[3:])

    view = {"k": gather(cache["k"], scales.get("k")),
            "v": gather(cache["v"], scales.get("v"))}
    out, view = model.decode_step(params, token, view, kv_len,
                                  return_hidden=return_hidden)
    blk = kv_len // P
    phys = jnp.take_along_axis(block_tables, blk[:, None], axis=1)[:, 0]
    cache, scales = dict(cache), dict(scales)
    for name in ("k", "v"):
        new = jax.vmap(lambda x, b: jax.lax.dynamic_slice_in_dim(
            x, b * P, P, axis=1), in_axes=(1, 0), out_axes=1)(view[name],
                                                              blk)
        if quant:
            new = new.astype(jnp.float32)
            s = jnp.maximum(scales[name][:, phys],
                            jnp.max(jnp.abs(new), axis=(2, 3, 4)) / 127.0)
            q8 = jnp.clip(jnp.round(new / s[:, :, None, None, None]),
                          -127, 127).astype(jnp.int8)
            cache[name] = cache[name].at[:, phys].set(q8)
            scales[name] = scales[name].at[:, phys].set(s)
        else:
            cache[name] = cache[name].at[:, phys].set(
                new.astype(cache[name].dtype))
    return out, cache, scales


def flash_attention_ref(q, k, v, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, seg_ids=None) -> jnp.ndarray:
    """(B, S, H, D) GQA causal attention oracle (packed via seg_ids)."""
    return L.full_attention(q, k, v, causal=causal, window=window,
                            softcap=softcap, seg_q=seg_ids, seg_k=seg_ids)


def quantize_pages_ref(pages: jnp.ndarray):
    """Per-page symmetric int8 quantization: (N, page, Kh, D) fp ->
    (int8 pages, (N,) f32 scales) with scale = amax / 127 (1e-8 floor, so
    all-zero pages round-trip exactly)."""
    amax = jnp.max(jnp.abs(pages.astype(jnp.float32)), axis=(1, 2, 3))
    scales = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(pages.astype(jnp.float32)
                           / scales[:, None, None, None]), -127, 127)
    return q.astype(jnp.int8), scales


def dequantize_pages_ref(pages: jnp.ndarray, scales: jnp.ndarray
                         ) -> jnp.ndarray:
    """(N, page, Kh, D) int8 x (N,) f32 -> f32 pages."""
    return pages.astype(jnp.float32) * scales[:, None, None, None]


def fused_sample_ref(x, w, softcap: float = 0.0):
    """Two-pass oracle for the fused sampling kernel: materialise the
    full (B, V) logits, then max / argmax / logsumexp, each (B, 1)."""
    logits = jnp.einsum("bd,dv->bv", x, w).astype(jnp.float32)
    if softcap > 0:
        logits = jnp.tanh(logits / softcap) * softcap
    val = jnp.max(logits, axis=-1, keepdims=True)
    idx = jnp.argmax(logits, axis=-1, keepdims=True).astype(jnp.int32)
    lse = jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    return val, idx, lse
