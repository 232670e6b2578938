"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
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
    This is the kernel-free path the engine uses on CPU: the paged Pallas
    kernel reads the same pages block-by-block instead of gathering.
    """
    B, nb = block_tables.shape
    g = jnp.take(pages, block_tables.reshape(-1), axis=0)
    return g.reshape(B, nb * pages.shape[1], *pages.shape[2:])


def paged_decode_attention_ref(q, k_pages, v_pages, block_tables, kv_len,
                               softcap: float = 0.0) -> jnp.ndarray:
    """(B, H, D) x (N, page, Kh, D) x (B, nb) x (B,) -> (B, H, D)."""
    return L.decode_attention(q, gather_pages(k_pages, block_tables),
                              gather_pages(v_pages, block_tables),
                              kv_len, softcap=softcap)


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


def paged_decode_attention_int8_ref(q, k_pages, v_pages, k_scales, v_scales,
                                    block_tables, kv_len,
                                    softcap: float = 0.0) -> jnp.ndarray:
    """int8-page oracle: dequantize the pools, then the fp paged ref."""
    return paged_decode_attention_ref(
        q, dequantize_pages_ref(k_pages, k_scales),
        dequantize_pages_ref(v_pages, v_scales), block_tables, kv_len,
        softcap=softcap)


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
