"""Dense decoder-only transformer (also the backbone for MoE / VLM archs).

Layer stacks are ``lax.scan`` over stacked parameters so HLO size (and AOT
compile time) is independent of depth.  Alternating layer patterns
(gemma2 local/global) stack as (L/pl, pl, ...) and unroll the inner ``pl``
sub-layers statically inside the scan body.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import logical_constraint
from repro.kernels import ops
from repro.models import layers as L

Params = Dict[str, Any]

FULL_ATTN_MAX_SEQ = 2048   # above this, use blockwise (flash-style) attention


def pattern_len(cfg: ModelConfig) -> int:
    return 2 if cfg.attn.layer_pattern == "local_global" else 1


def _sub_window(cfg: ModelConfig, j: int) -> int:
    """Sliding window for sub-layer j of a pattern group (0 = full attn)."""
    if cfg.attn.layer_pattern == "local_global":
        return cfg.attn.sliding_window if j == 0 else 0
    return cfg.attn.sliding_window


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, dtype,
               mlp_init: Optional[Callable] = None) -> Params:
    ka, km, kn1, kn2 = jax.random.split(key, 4)
    mlp_init = mlp_init or (lambda k: L.init_mlp(
        k, cfg.d_model, cfg.d_ff, cfg.gated_mlp, cfg.num_layers, dtype))
    return {
        "attn": L.init_attention(ka, cfg, dtype),
        "mlp": mlp_init(km),
        "ln1": L.init_norm(kn1, cfg.d_model, cfg.norm_type, dtype),
        "ln2": L.init_norm(kn2, cfg.d_model, cfg.norm_type, dtype),
    }


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_params(cfg: ModelConfig, key,
                mlp_init: Optional[Callable] = None) -> Params:
    pl = pattern_len(cfg)
    n_groups = cfg.num_layers // pl
    keys = jax.random.split(key, cfg.num_layers + 3)
    dtype = cfg.param_dtype
    blocks = [init_block(keys[i], cfg, dtype, mlp_init)
              for i in range(cfg.num_layers)]
    if pl == 2:
        groups = [_stack([blocks[2 * i], blocks[2 * i + 1]])
                  for i in range(n_groups)]
    else:
        groups = blocks
    params: Params = {
        "embed": (jax.random.normal(keys[-1], (cfg.vocab_size, cfg.d_model))
                  * (1.0 / math.sqrt(cfg.d_model))).astype(dtype),
        "layers": _stack(groups),
        "final_norm": L.init_norm(keys[-2], cfg.d_model, cfg.norm_type, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(
            keys[-3], (cfg.d_model, cfg.vocab_size))
            * (1.0 / math.sqrt(cfg.d_model))).astype(dtype)
    if cfg.pos_embedding == "learned":
        params["pos_embed"] = (jax.random.normal(
            keys[-3], (cfg.max_position, cfg.d_model)) * 0.02).astype(dtype)
    return params


# ---------------------------------------------------------------------------
# Shared block application
# ---------------------------------------------------------------------------

ZERO_AUX = {"load_balance": jnp.float32(0.0), "router_z": jnp.float32(0.0)}


def _apply_mlp(bp, cfg, h, mlp_fn):
    """Returns (y, aux).  ``mlp_fn(params, x) -> (y, aux)`` (MoE) or dense."""
    if mlp_fn is not None:
        out = mlp_fn(bp["mlp"], h)
        if isinstance(out, tuple):
            return out
        return out, dict(ZERO_AUX)
    return L.mlp(bp["mlp"], h, cfg.mlp_act, cfg.gated_mlp), dict(ZERO_AUX)


def block_forward(bp: Params, cfg: ModelConfig, x: jnp.ndarray,
                  positions: jnp.ndarray, window: int,
                  mlp_fn: Optional[Callable] = None):
    """Full-sequence (training / prefill) block.  Returns (x, aux)."""
    h = L.norm(x, bp["ln1"], cfg.norm_type, cfg.norm_eps)
    q, k, v = L.qkv_project(bp["attn"], cfg, h, positions)
    S = q.shape[1]
    if S <= FULL_ATTN_MAX_SEQ:
        o = L.full_attention(q, k, v, causal=True, window=window,
                             softcap=cfg.attn.attn_softcap)
    else:
        o = L.blockwise_attention(q, k, v, causal=True, window=window,
                                  softcap=cfg.attn.attn_softcap)
    x = x + L.attn_output(bp["attn"], o)
    h = L.norm(x, bp["ln2"], cfg.norm_type, cfg.norm_eps)
    y, aux = _apply_mlp(bp, cfg, h, mlp_fn)
    x = x + y
    return logical_constraint(x, ("batch", "seq", "embed")), aux


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embed_tokens(params: Params, cfg: ModelConfig, tokens: jnp.ndarray
                 ) -> jnp.ndarray:
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)
    if cfg.scale_embeddings:
        x = x * math.sqrt(cfg.d_model)
    return logical_constraint(x, ("batch", "seq", "embed"))


def lm_logits(params: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    x = L.norm(x, params["final_norm"], cfg.norm_type, cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("...d,dv->...v", x, w.astype(cfg.compute_dtype))
    if cfg.logit_softcap > 0:
        logits = L._softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    axes = (("batch", "seq_out", "vocab") if logits.ndim == 3
            else ("batch", "vocab"))
    return logical_constraint(logits, axes)


# ---------------------------------------------------------------------------
# Forward (training / scoring): full sequence -> logits
# ---------------------------------------------------------------------------

def forward(params: Params, cfg: ModelConfig, tokens: Optional[jnp.ndarray] = None,
            embeds: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None,
            mlp_fn: Optional[Callable] = None):
    """Returns (logits, aux) where aux holds summed router losses."""
    x = embed_tokens(params, cfg, tokens) if embeds is None else embeds
    B, S = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.pos_embedding == "learned":
        x = x + params["pos_embed"][:S][None].astype(x.dtype)
    pl = pattern_len(cfg)

    def body(carry, group):
        h, aux_sum = carry
        for j in range(pl):
            bp = jax.tree.map(lambda a: a[j], group) if pl == 2 else group
            h, aux = block_forward(bp, cfg, h, positions, _sub_window(cfg, j),
                                   mlp_fn=mlp_fn)
            aux_sum = jax.tree.map(jnp.add, aux_sum, aux)
        return (h, aux_sum), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, dict(ZERO_AUX)), params["layers"])
    return lm_logits(params, cfg, x), aux


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Dict[str, jnp.ndarray]:
    dtype = dtype or cfg.compute_dtype
    Kh, D = cfg.num_kv_heads, cfg.resolved_head_dim
    pl = pattern_len(cfg)
    n_groups = cfg.num_layers // pl
    if pl == 2:
        W = min(cfg.attn.sliding_window, max_len)
        return {
            "k_local": jnp.zeros((n_groups, batch, W, Kh, D), dtype),
            "v_local": jnp.zeros((n_groups, batch, W, Kh, D), dtype),
            "k_global": jnp.zeros((n_groups, batch, max_len, Kh, D), dtype),
            "v_global": jnp.zeros((n_groups, batch, max_len, Kh, D), dtype),
        }
    return {
        "k": jnp.zeros((cfg.num_layers, batch, max_len, Kh, D), dtype),
        "v": jnp.zeros((cfg.num_layers, batch, max_len, Kh, D), dtype),
    }


def _write_token(cache_layer: jnp.ndarray, new: jnp.ndarray,
                 idx: jnp.ndarray) -> jnp.ndarray:
    """cache_layer: (Lg, B, S, Kh, D); new: (Lg, B, Kh, D); idx: (B,)."""
    b = jnp.arange(cache_layer.shape[1])
    return cache_layer.at[:, b, idx].set(new.astype(cache_layer.dtype))


# ---------------------------------------------------------------------------
# Decode step: one token, scan over layers
# ---------------------------------------------------------------------------

def _decode_layer(group: Params, cfg: ModelConfig, h: jnp.ndarray,
                  kv_len: jnp.ndarray, attend: Callable,
                  mlp_fn: Optional[Callable]):
    """One layer of a one-token decode step.  ``attend(q, k, v)`` gets
    the token's q (B, H, D) and k, v (B, Kh, D) and returns the attention
    output (B, H, D).  Returns (h, k, v)."""
    hn = L.norm(h, group["ln1"], cfg.norm_type, cfg.norm_eps)
    q, k, v = L.qkv_project(group["attn"], cfg, hn, kv_len[:, None])
    k, v = k[:, 0], v[:, 0]
    h = h + L.attn_output(group["attn"], attend(q[:, 0], k, v)[:, None])
    hn = L.norm(h, group["ln2"], cfg.norm_type, cfg.norm_eps)
    y, _ = _apply_mlp(group, cfg, hn, mlp_fn)
    return h + y, k, v


def _decode_input(params: Params, cfg: ModelConfig, token, kv_len, embeds):
    if embeds is None:
        x = embed_tokens(params, cfg, token[:, None])
    else:
        x = embeds[:, None] if embeds.ndim == 2 else embeds
    if cfg.pos_embedding == "learned":
        x = x + params["pos_embed"][kv_len][:, None].astype(x.dtype)
    return x


def _decode_output(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                   return_hidden: bool) -> jnp.ndarray:
    if return_hidden:
        return L.norm(x[:, 0], params["final_norm"], cfg.norm_type,
                      cfg.norm_eps)
    return lm_logits(params, cfg, x[:, 0])


def decode_step(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                cache: Dict[str, jnp.ndarray], kv_len: jnp.ndarray,
                mlp_fn: Optional[Callable] = None,
                embeds: Optional[jnp.ndarray] = None,
                return_hidden: bool = False,
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """token: (B,) int32; kv_len: (B,) current lengths (position of the new
    token).  Returns (logits (B, V), updated cache).

    ``return_hidden=True`` returns the final-normed hidden state (B, d)
    instead of logits — the fused-sampling decode path computes the LM
    head blockwise in the same pass as max/lse, so the full (B, V)
    logits round-trip never materialises (see rollout/engine.py)."""
    x = _decode_input(params, cfg, token, kv_len, embeds)
    if pattern_len(cfg) == 2:
        raise ValueError("use decode_step_pattern for local/global archs")

    # write the new token's k/v first, then attend over the cache.
    # Pass cache slices as scan xs; collect per-layer new kv as ys.
    def body(h, xs):
        group, kc, vc = xs

        def attend(q, k, v):
            kc_ = _write_token(kc[None], k[None], kv_len)[0]
            vc_ = _write_token(vc[None], v[None], kv_len)[0]
            return L.decode_attention(q, kc_, vc_, kv_len + 1,
                                      softcap=cfg.attn.attn_softcap,
                                      window=cfg.attn.sliding_window)

        h, k, v = _decode_layer(group, cfg, h, kv_len, attend, mlp_fn)
        return h, (k, v)

    x, (k_new, v_new) = jax.lax.scan(body, x, (params["layers"],
                                               cache["k"], cache["v"]))
    cache = dict(cache)
    cache["k"] = _write_token(cache["k"], k_new, kv_len)
    cache["v"] = _write_token(cache["v"], v_new, kv_len)
    return _decode_output(params, cfg, x, return_hidden), cache


def decode_step_paged(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                      pages: Dict[str, jnp.ndarray],
                      scales: Dict[str, jnp.ndarray],
                      block_tables: jnp.ndarray, kv_len: jnp.ndarray,
                      mlp_fn: Optional[Callable] = None,
                      return_hidden: bool = False):
    """One decode step on a page pool, read in place.

    pages: {"k", "v"} (L, N, page, Kh, D); scales: {} or an int8 pool's
    {"k", "v"} (L, N) f32; block_tables: (B, nb) physical pages of each
    slot; kv_len: (B,) rows in the pool, the new token's position.  Each
    layer's attention reads that layer's pages through the block tables
    (``kernels.ops.paged_attention``) and folds in the new token, which is
    not written here.  Returns (logits (B, V), or the final-normed hidden
    state (B, d) with ``return_hidden``, and (k_new, v_new), each
    (L, B, Kh, D) in the compute dtype): the caller writes those rows at
    ``kv_len``."""
    assert pattern_len(cfg) == 1 and not cfg.attn.sliding_window, \
        "paged decode: every layer attends over its whole context"
    x = _decode_input(params, cfg, token, kv_len, None)

    def body(h, xs):
        group, layer = xs

        def attend(q, k, v):
            with jax.named_scope("attend"):
                return ops.paged_attention(
                    q, pages["k"], pages["v"], block_tables, kv_len, layer,
                    k.astype(cfg.compute_dtype), v.astype(cfg.compute_dtype),
                    softcap=cfg.attn.attn_softcap, k_scales=scales.get("k"),
                    v_scales=scales.get("v"))

        h, k, v = _decode_layer(group, cfg, h, kv_len, attend, mlp_fn)
        return h, (k.astype(cfg.compute_dtype), v.astype(cfg.compute_dtype))

    x, kv_new = jax.lax.scan(body, x, (params["layers"],
                                       jnp.arange(cfg.num_layers)))
    return _decode_output(params, cfg, x, return_hidden), kv_new


def decode_step_pattern(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                        cache: Dict[str, jnp.ndarray], kv_len: jnp.ndarray,
                        mlp_fn: Optional[Callable] = None,
                        ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Decode for local/global alternating pattern (gemma2)."""
    x = embed_tokens(params, cfg, token[:, None])
    W = cache["k_local"].shape[2]
    ring_idx = kv_len % W

    def body(h, xs):
        group, kl, vl, kg, vg = xs
        # --- local sub-layer: ring cache of width W ---
        bp = jax.tree.map(lambda a: a[0], group)
        hn = L.norm(h, bp["ln1"], cfg.norm_type, cfg.norm_eps)
        q, k, v = L.qkv_project(bp["attn"], cfg, hn, kv_len[:, None])
        kl = _write_token(kl[None], k[None, :, 0], ring_idx)[0]
        vl = _write_token(vl[None], v[None, :, 0], ring_idx)[0]
        o = L.decode_attention(q[:, 0], kl, vl, jnp.minimum(kv_len + 1, W),
                               softcap=cfg.attn.attn_softcap)
        h = h + L.attn_output(bp["attn"], o[:, None])
        hn = L.norm(h, bp["ln2"], cfg.norm_type, cfg.norm_eps)
        h = h + L.mlp(bp["mlp"], hn, cfg.mlp_act, cfg.gated_mlp)
        # --- global sub-layer: linear cache ---
        bp = jax.tree.map(lambda a: a[1], group)
        hn = L.norm(h, bp["ln1"], cfg.norm_type, cfg.norm_eps)
        q2, k2, v2 = L.qkv_project(bp["attn"], cfg, hn, kv_len[:, None])
        kg = _write_token(kg[None], k2[None, :, 0], kv_len)[0]
        vg = _write_token(vg[None], v2[None, :, 0], kv_len)[0]
        o2 = L.decode_attention(q2[:, 0], kg, vg, kv_len + 1,
                                softcap=cfg.attn.attn_softcap)
        h = h + L.attn_output(bp["attn"], o2[:, None])
        hn = L.norm(h, bp["ln2"], cfg.norm_type, cfg.norm_eps)
        h = h + L.mlp(bp["mlp"], hn, cfg.mlp_act, cfg.gated_mlp)
        return h, (k[:, 0], v[:, 0], k2[:, 0], v2[:, 0])

    x, (kl_n, vl_n, kg_n, vg_n) = jax.lax.scan(
        body, x, (params["layers"], cache["k_local"], cache["v_local"],
                  cache["k_global"], cache["v_global"]))
    cache = {
        "k_local": _write_token(cache["k_local"], kl_n, ring_idx),
        "v_local": _write_token(cache["v_local"], vl_n, ring_idx),
        "k_global": _write_token(cache["k_global"], kg_n, kv_len),
        "v_global": _write_token(cache["v_global"], vg_n, kv_len),
    }
    logits = lm_logits(params, cfg, x[:, 0])
    return logits, cache


def decode(params, cfg, token, cache, kv_len, mlp_fn=None, embeds=None,
           return_hidden=False):
    if pattern_len(cfg) == 2:
        assert not return_hidden, "return_hidden: local/global not supported"
        return decode_step_pattern(params, cfg, token, cache, kv_len, mlp_fn)
    return decode_step(params, cfg, token, cache, kv_len, mlp_fn, embeds,
                       return_hidden=return_hidden)


# ---------------------------------------------------------------------------
# Prefill: run full (padded) prompts through, filling the cache
# ---------------------------------------------------------------------------

def prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
            cache: Dict[str, jnp.ndarray], prompt_lens: jnp.ndarray,
            mlp_fn: Optional[Callable] = None,
            embeds: Optional[jnp.ndarray] = None,
            seg_ids: Optional[jnp.ndarray] = None,
            positions: Optional[jnp.ndarray] = None,
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """tokens: (B, S) right-padded prompts.  Fills cache[:, :, :S]; returns
    (logits at each position (B, S, V), cache).  Padded positions are
    masked downstream via kv_len = prompt_lens.

    Packed mode (``seg_ids`` given): each row holds several prompts
    concatenated back to back; ``seg_ids`` (B, S) carries the row-local
    segment index (-1 for padding) and ``positions`` the within-segment
    position of every token (rope / learned pos-emb see per-prompt
    coordinates).  Attention masks across segment boundaries; the causal
    and sliding-window masks stay correct under the packed global arange
    because segments are contiguous, so global position deltas equal
    within-segment deltas."""
    x = embed_tokens(params, cfg, tokens) if embeds is None else embeds
    B, S = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.pos_embedding == "learned":
        if seg_ids is None:
            x = x + params["pos_embed"][:S][None].astype(x.dtype)
        else:
            x = x + params["pos_embed"][positions].astype(x.dtype)
    pl = pattern_len(cfg)

    if pl == 2:
        assert seg_ids is None, "packed prefill: local/global not supported"
        W = cache["k_local"].shape[2]

        def body(h, xs):
            group, kl, vl, kg, vg = xs
            outs = []
            for j, (kc, vc) in enumerate(((kl, vl), (kg, vg))):
                bp = jax.tree.map(lambda a: a[j], group)
                hn = L.norm(h, bp["ln1"], cfg.norm_type, cfg.norm_eps)
                q, k, v = L.qkv_project(bp["attn"], cfg, hn, positions)
                window = _sub_window(cfg, j)
                if S <= FULL_ATTN_MAX_SEQ:
                    o = L.full_attention(q, k, v, causal=True, window=window,
                                         softcap=cfg.attn.attn_softcap)
                else:
                    o = L.blockwise_attention(q, k, v, causal=True,
                                              window=window,
                                              softcap=cfg.attn.attn_softcap)
                h = h + L.attn_output(bp["attn"], o)
                hn = L.norm(h, bp["ln2"], cfg.norm_type, cfg.norm_eps)
                h = h + L.mlp(bp["mlp"], hn, cfg.mlp_act, cfg.gated_mlp)
                if j == 0:
                    # ring cache: slot for position p is p % W; keep last W
                    if S <= W:
                        kc = kc.at[:, :S].set(k.astype(kc.dtype))
                        vc = vc.at[:, :S].set(v.astype(vc.dtype))
                    else:
                        idx = jnp.arange(S - W, S) % W
                        kc = kc.at[:, idx].set(k[:, S - W:].astype(kc.dtype))
                        vc = vc.at[:, idx].set(v[:, S - W:].astype(vc.dtype))
                else:
                    kc = kc.at[:, :S].set(k.astype(kc.dtype))
                    vc = vc.at[:, :S].set(v.astype(vc.dtype))
                outs.append((kc, vc))
            return h, (outs[0][0], outs[0][1], outs[1][0], outs[1][1])

        x, (kl, vl, kg, vg) = jax.lax.scan(
            body, x, (params["layers"], cache["k_local"], cache["v_local"],
                      cache["k_global"], cache["v_global"]))
        cache = {"k_local": kl, "v_local": vl, "k_global": kg, "v_global": vg}
    else:
        def body(h, xs):
            group, kc, vc = xs
            hn = L.norm(h, group["ln1"], cfg.norm_type, cfg.norm_eps)
            q, k, v = L.qkv_project(group["attn"], cfg, hn, positions)
            if S <= FULL_ATTN_MAX_SEQ:
                o = L.full_attention(q, k, v, causal=True,
                                     window=cfg.attn.sliding_window,
                                     softcap=cfg.attn.attn_softcap,
                                     seg_q=seg_ids, seg_k=seg_ids)
            else:
                o = L.blockwise_attention(q, k, v, causal=True,
                                          window=cfg.attn.sliding_window,
                                          softcap=cfg.attn.attn_softcap,
                                          seg_q=seg_ids, seg_k=seg_ids)
            h = h + L.attn_output(group["attn"], o)
            hn = L.norm(h, group["ln2"], cfg.norm_type, cfg.norm_eps)
            y, _ = _apply_mlp(group, cfg, hn, mlp_fn)
            h = h + y
            kc = kc.at[:, :S].set(k.astype(kc.dtype))
            vc = vc.at[:, :S].set(v.astype(vc.dtype))
            return h, (kc, vc)

        x, (kc, vc) = jax.lax.scan(body, x, (params["layers"],
                                             cache["k"], cache["v"]))
        cache = dict(cache, k=kc, v=vc)
    logits = lm_logits(params, cfg, x)
    return logits, cache
