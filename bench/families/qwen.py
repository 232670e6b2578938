"""The Qwen2 / Qwen3 dense decoder family (``model_type`` ``qwen2``,
``qwen3``): grouped-query attention with rotate-half RoPE, an optional
bias on q, k and v (Qwen2) or an RMSNorm on each query and key head
(Qwen3), a gated SiLU MLP, RMSNorm before each block, and an optionally
tied LM head.  The family contract is in :mod:`bench.families`.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from bench.reference import F32, mm

# the harness's CPU stand-in: two layers of 64, 4 / 2 heads of 16
TINY = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256}


def model_config(name: str, c: Dict):
    from repro.configs.base import AttnConfig, ModelConfig
    arch, prog = c["architecture"], c["program"]
    assert c["hidden_act"] == "silu" and not c.get("use_sliding_window"), c
    assert c.get("rope_scaling") is None, c
    return ModelConfig(
        name=name, family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=c.get("head_dim")
        or c["hidden_size"] // c["num_attention_heads"],
        attn=AttnConfig(qk_norm=arch["qk_norm"], qkv_bias=arch["qkv_bias"],
                        rope_theta=float(c["rope_theta"])),
        mlp_act="silu", gated_mlp=True, norm_type="rmsnorm",
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=c["tie_word_embeddings"],
        remat=prog["remat"],
        param_dtype=jnp.dtype(prog["param_dtype"]),
        compute_dtype=jnp.dtype(prog["compute_dtype"]),
        source=c["source"])


def init_std(name: str, init_range: float) -> Tuple[float, float]:
    """(mean, std) of a leaf.  Norm scales sit near 1 and biases are small
    but not zero, so a path that drops either shows in the comparison."""
    last = name.rsplit("/", 1)[-1]
    if last in ("scale", "q_norm", "k_norm"):
        return 1.0, 0.1
    if last in ("bq", "bk", "bv"):
        return 0.0, 0.1
    return 0.0, init_range


# -- the plain float32 forward ------------------------------------------------

def _rms(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, positions, theta):
    """Rotate-half RoPE (HF Qwen2/Qwen3): pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None]          # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Forward:
    """The published Qwen2/Qwen3 decoder; ``c`` is the configuration
    file's dict (published keys)."""

    def __init__(self, c: Dict, mode: str):
        self.mode = mode
        self.H = c["num_attention_heads"]
        self.Kh = c["num_key_value_heads"]
        self.D = c.get("head_dim") or c["hidden_size"] // self.H
        self.eps = float(c["rms_norm_eps"])
        self.theta = float(c["rope_theta"])
        self.qk_norm = c["architecture"]["qk_norm"]
        self.tied = c["tie_word_embeddings"]

    def _layer(self, x, p):
        mode, eps = self.mode, self.eps
        S = x.shape[1]
        pos = jnp.arange(S)
        a = p["attn"]
        h = _rms(x, p["ln1"]["scale"], eps)
        q = mm("bsd,dhk->bshk", h, a["wq"], mode)
        k = mm("bsd,dhk->bshk", h, a["wk"], mode)
        v = mm("bsd,dhk->bshk", h, a["wv"], mode)
        if "bq" in a:
            q, k, v = (q + a["bq"].astype(F32), k + a["bk"].astype(F32),
                       v + a["bv"].astype(F32))
        if self.qk_norm:
            q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
        q, k = _rope(q, pos, self.theta), _rope(k, pos, self.theta)
        g = self.H // self.Kh                 # query head i reads kv head i//g
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        s = mm("bqhd,bkhd->bhqk", q, k, mode) / math.sqrt(self.D)
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v, mode)
        x = x + mm("bshk,hkd->bsd", o, a["wo"], mode)
        h = _rms(x, p["ln2"]["scale"], eps)
        m = p["mlp"]
        u = jax.nn.silu(mm("bsd,df->bsf", h, m["w_in"], mode)) \
            * mm("bsd,df->bsf", h, m["w_gate"], mode)
        return x + mm("bsf,fd->bsd", u, m["w_out"], mode), None

    def logits(self, params, tokens):
        """tokens (B, S) -> float32 logits (B, S, V)."""
        x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
        x, _ = jax.lax.scan(jax.checkpoint(self._layer), x, params["layers"])
        x = _rms(x, params["final_norm"]["scale"], self.eps)
        if self.tied:
            return mm("bsd,vd->bsv", x, params["embed"], self.mode)
        return mm("bsd,dv->bsv", x, params["lm_head"], self.mode)


# -- operations and bytes the work requires, from shapes alone ----------------
#
# A dense decoder layer multiplies each token by its q, k, v, o and gated MLP
# matrices (2 FLOPs per weight), and attention costs 4 * H * D FLOPs per
# (query, key) pair (scores and the weighted sum).  The LM head is 2 * d * V
# per token.

def dims(c: Dict) -> Tuple[int, int, int, int, int, int, int]:
    H = c["num_attention_heads"]
    D = c.get("head_dim") or c["hidden_size"] // H
    return (c["num_hidden_layers"], c["hidden_size"], H,
            c["num_key_value_heads"], D, c["intermediate_size"],
            c["vocab_size"])


def layer_matmul_params(c: Dict) -> int:
    L, d, H, Kh, D, ff, V = dims(c)
    return d * H * D + 2 * d * Kh * D + H * D * d + 3 * d * ff


def param_count(c: Dict) -> int:
    L, d, H, Kh, D, ff, V = dims(c)
    per = layer_matmul_params(c) + 2 * d
    if c["architecture"]["qk_norm"]:
        per += 2 * D
    if c["architecture"]["qkv_bias"]:
        per += (H + 2 * Kh) * D
    n = V * d + L * per + d
    if not c["tie_word_embeddings"]:
        n += V * d
    return n


def param_bytes(c: Dict) -> int:
    return 2 * param_count(c)                       # bf16


def kv_bytes_per_token(c: Dict) -> int:
    L, d, H, Kh, D, ff, V = dims(c)
    return 2 * L * Kh * D * 2                       # K and V, bf16


def forward_flops(c: Dict, tokens: float, ctx_sum: float) -> float:
    """``tokens`` tokens whose attention reads ``ctx_sum`` keys in all."""
    L, d, H, Kh, D, ff, V = dims(c)
    return (2.0 * L * layer_matmul_params(c) * tokens
            + 4.0 * L * H * D * ctx_sum + 2.0 * d * V * tokens)


def decode_step_least(c: Dict, contexts: Sequence[int],
                      peak: Dict[str, float]) -> float:
    """Least seconds of one decode step over active slots with these
    context lengths: the larger of its FLOPs over peak and its least bytes
    (the weights once, plus the live K/V of each slot) over bandwidth."""
    flops = forward_flops(c, len(contexts), sum(contexts))
    bytes_ = param_bytes(c) + kv_bytes_per_token(c) * sum(contexts)
    return max(flops / peak["bf16_flops"], bytes_ / peak["hbm_bytes_per_s"])
