"""Jitted public wrappers around the Pallas kernels.

Each kernel runs compiled on a TPU and in Pallas interpret mode when
JAX's default backend is the CPU (:func:`repro.kernels.default_interpret`,
resolved at trace time); the tests compare these wrappers with the
oracles in ``ref.py``.  ``paged_attention`` is the paged decode's
attention as the model calls it (not jitted: it runs inside the decode
program; the tests and ``chip_smoke.py`` jit it themselves).
"""
from __future__ import annotations

import functools

import jax

from repro import kernels
from repro.kernels import ref
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


def paged_attention(q, k_pages, v_pages, block_tables, kv_len, layer, k_new,
                    v_new, *, softcap: float = 0.0, k_scales=None,
                    v_scales=None):
    """The decode step's attention as the engine serves it: the Pallas
    kernel, compiled, on a TPU; its jnp reference where the kernel could
    only be interpreted (the CPU backend, so an engine step there never
    runs the Pallas interpreter) or cannot compile: a head_dim that is not
    a multiple of the 128 lanes, whose page copies Mosaic refuses.
    Chosen at trace time from :func:`repro.kernels.default_interpret` and
    the pool's shape; arguments as the kernel's
    (``ragged_decode_attention.paged_decode_attention``), an int8 pool's
    scales as ``k_scales``/``v_scales``."""
    if kernels.default_interpret() or k_pages.shape[-1] % 128:
        return ref.paged_decode_attention_ref(
            q, k_pages, v_pages, block_tables, kv_len, layer, k_new, v_new,
            softcap=softcap, k_scales=k_scales, v_scales=v_scales)
    return _paged(q, k_pages, v_pages, block_tables, kv_len, layer, k_new,
                  v_new, softcap=softcap, k_scales=k_scales,
                  v_scales=v_scales, interpret=False)


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
