"""Jitted public wrappers around the Pallas kernels.

Each kernel runs compiled on a TPU and in Pallas interpret mode when
JAX's default backend is the CPU (:func:`repro.kernels.default_interpret`,
resolved at trace time).  The model layers call the pure-jnp paths; the
tests compare these wrappers with the oracles in ``ref.py``.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.ragged_decode_attention import (
    fused_sample as _fused_sample)
from repro.kernels.ragged_decode_attention import (
    paged_decode_attention as _paged)
from repro.kernels.ragged_decode_attention import (
    ragged_decode_attention as _ragged)


@functools.partial(jax.jit, static_argnames=("block_k", "softcap"))
def ragged_decode_attention(q, k_cache, v_cache, kv_len, block_k: int = 128,
                            softcap: float = 0.0):
    return _ragged(q, k_cache, v_cache, kv_len, block_k=block_k,
                   softcap=softcap)


@functools.partial(jax.jit, static_argnames=("softcap",))
def paged_decode_attention(q, k_pages, v_pages, block_tables, kv_len,
                           softcap: float = 0.0):
    return _paged(q, k_pages, v_pages, block_tables, kv_len,
                  softcap=softcap)


@functools.partial(jax.jit, static_argnames=("softcap",))
def paged_decode_attention_int8(q, k_pages, v_pages, k_scales, v_scales,
                                block_tables, kv_len, softcap: float = 0.0):
    """Paged decode over int8-quantized pages with per-page f32 scales."""
    return _paged(q, k_pages, v_pages, block_tables, kv_len,
                  softcap=softcap, k_scales=k_scales, v_scales=v_scales)


@functools.partial(jax.jit, static_argnames=("block_v", "softcap"))
def fused_sample(x, w, block_v: int = 128, softcap: float = 0.0):
    """Fused LM-head matmul + greedy token + logsumexp (no (B, V)
    round-trip)."""
    return _fused_sample(x, w, block_v=block_v, softcap=softcap)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "window",
                                    "softcap"))
def flash_attention(q, k, v, seg_ids=None, block_q: int = 128,
                    block_k: int = 128, window: int = 0,
                    softcap: float = 0.0):
    return _flash(q, k, v, seg_ids=seg_ids, block_q=block_q,
                  block_k=block_k, window=window, softcap=softcap)
