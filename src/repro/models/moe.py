"""Mixture-of-Experts FFN (top-k routing, capacity-based token drop).

Two execution paths, both validated against a loop-over-experts oracle:

* ``moe_mlp_dense`` — GShard-style one-hot dispatch einsum.  Used for small
  token counts (decode steps, CPU smoke tests).  Memory O(T * E * C).
* ``moe_mlp_ep``   — expert-parallel path for training/prefill at scale:
  a ``shard_map`` region where tokens are split over (data, model), each
  device builds fixed-capacity per-expert buffers, and ``all_to_all`` over
  the ``model`` axis moves token buffers to the devices owning the experts
  (classic DeepSpeed-MoE/EP layout, TPU-native: the all-to-all is exactly
  the collective the roofline must see).

Router aux losses (load-balance + z-loss) are accumulated into a host of
side outputs threaded through as an explicit return.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

Params = Dict[str, jnp.ndarray]


def init_moe_mlp(key, cfg: ModelConfig, dtype) -> Params:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff_expert, m.num_experts
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    sd_in = 1.0 / math.sqrt(d)
    sd_out = 1.0 / math.sqrt(f * 2 * cfg.num_layers)
    p = {
        "router": (jax.random.normal(k1, (d, E)) * sd_in).astype(jnp.float32),
        "w_in": (jax.random.normal(k2, (E, d, f)) * sd_in).astype(dtype),
        "w_gate": (jax.random.normal(k3, (E, d, f)) * sd_in).astype(dtype),
        "w_out": (jax.random.normal(k4, (E, f, d)) * sd_out).astype(dtype),
    }
    if m.d_ff_shared:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(k5, d, m.d_ff_shared, True, cfg.num_layers, dtype)
    return p


def _route(p: Params, cfg: ModelConfig, x2d: jnp.ndarray):
    """x2d: (T, d) -> (gates (T,k) f32, idx (T,k) int32, aux (dict))."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.experts_per_token)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # aux losses (Switch-style load balance + z loss)
    density = jnp.mean(jax.nn.one_hot(idx[:, 0], m.num_experts), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    aux = {
        "load_balance": m.num_experts * jnp.sum(density * mean_probs),
        "router_z": jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))),
    }
    return gates, idx, aux


def _capacity(cfg: ModelConfig, T: int) -> int:
    m = cfg.moe
    c = int(math.ceil(m.capacity_factor * m.experts_per_token * T
                      / m.num_experts))
    return max(4, c)


def _dispatch_indices(idx: jnp.ndarray, E: int, C: int):
    """idx: (T, k) expert ids.  Returns (pos (T,k) slot-in-expert,
    keep (T,k) bool) computed in routing order with capacity C."""
    T, k = idx.shape
    flat = idx.reshape(-1)                                   # (T*k,)
    onehot = jax.nn.one_hot(flat, E, dtype=jnp.int32)        # (T*k, E)
    pos_flat = jnp.cumsum(onehot, axis=0) - onehot           # slot before me
    pos = jnp.take_along_axis(pos_flat, flat[:, None], axis=1)[:, 0]
    keep = pos < C
    return pos.reshape(T, k), keep.reshape(T, k)


def _expert_ffn(p: Params, xe: jnp.ndarray, act: str) -> jnp.ndarray:
    """xe: (E, C, d) -> (E, C, d)."""
    h = jnp.einsum("ecd,edf->ecf", xe, p["w_in"])
    if act == "silu":
        a = jax.nn.silu(h)
    elif act == "relu2":
        a = jnp.square(jax.nn.relu(h))
    else:
        a = jax.nn.gelu(h)
    a = a * jnp.einsum("ecd,edf->ecf", xe, p["w_gate"])
    return jnp.einsum("ecf,efd->ecd", a, p["w_out"])


def moe_mlp_dense(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Capacity-based scatter/gather MoE on whatever tokens are local.

    x: (B, S, d) -> (B, S, d).  Suitable for small T (decode / smoke).
    """
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    x2d = x.reshape(T, d)
    gates, idx, aux = _route(p, cfg, x2d)
    C = _capacity(cfg, T)
    pos, keep = _dispatch_indices(idx, m.num_experts, C)
    buf = jnp.zeros((m.num_experts, C, d), x.dtype)
    for j in range(m.experts_per_token):           # k small & static
        contrib = jnp.where(keep[:, j, None], x2d, 0).astype(x.dtype)
        buf = buf.at[idx[:, j], jnp.where(keep[:, j], pos[:, j], C - 1)].add(
            jnp.where(keep[:, j, None], contrib, 0))
    out_e = _expert_ffn(p, buf, cfg.mlp_act)       # (E, C, d)
    y2d = jnp.zeros((T, d), jnp.float32)
    for j in range(m.experts_per_token):
        gathered = out_e[idx[:, j], jnp.minimum(pos[:, j], C - 1)]
        y2d = y2d + jnp.where(keep[:, j, None],
                              gathered.astype(jnp.float32)
                              * gates[:, j, None], 0.0)
    y = y2d.reshape(B, S, d).astype(x.dtype)
    if "shared" in p:
        from repro.models.layers import mlp as dense_mlp
        y = y + dense_mlp(p["shared"], x, "silu", True)
    return y, aux


def moe_mlp_ep(p: Params, cfg: ModelConfig, x: jnp.ndarray, mesh,
               data_axes=("data",), model_axis: str = "model",
               ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Expert-parallel MoE via shard_map + all_to_all over `model`.

    x: (B, S, d) with batch sharded over ``data_axes``.  Inside the region
    the sequence is additionally split over ``model`` so each device routes
    its own token slice; per-expert capacity buffers are exchanged with
    all_to_all so the device owning expert e computes all its tokens.
    """
    m = cfg.moe
    E = m.num_experts
    n_model = mesh.shape[model_axis]
    # pad the expert axis up to a multiple of the model axis (granite: 40
    # experts on 16-way EP -> 48 with 8 never-routed dummies)
    E_pad = -(-E // n_model) * n_model
    E_local = E_pad // n_model

    def local_fn(p_local, x_local):
        # x_local: (B_l, S_l, d); experts sharded: w_* (E_local, ...)
        B_l, S_l, d = x_local.shape
        T_l = B_l * S_l
        x2d = x_local.reshape(T_l, d)
        logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                            p_local["router"])   # router replicated
        if E_pad > E:
            logits = jnp.pad(logits, ((0, 0), (0, E_pad - E)),
                             constant_values=-1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, m.experts_per_token)
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
        density = jnp.mean(jax.nn.one_hot(idx[:, 0], E), axis=0)
        mean_probs = jnp.mean(probs[:, :E], axis=0)
        aux = {
            "load_balance": jax.lax.pmean(
                E * jnp.sum(density * mean_probs), model_axis),
            "router_z": jax.lax.pmean(
                jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1))), model_axis),
        }
        C = _capacity(cfg, T_l)
        pos, keep = _dispatch_indices(idx, E_pad, C)
        buf = jnp.zeros((E_pad, C, d), x_local.dtype)
        for j in range(m.experts_per_token):
            safe_pos = jnp.where(keep[:, j], pos[:, j], C - 1)
            contrib = jnp.where(keep[:, j, None], x2d, 0).astype(x_local.dtype)
            buf = buf.at[idx[:, j], safe_pos].add(contrib)
        # (E, C, d) -> all_to_all: send expert-owner chunks, receive every
        # source's buffer for my local experts.
        buf = buf.reshape(n_model, E_local, C, d)
        recv = jax.lax.all_to_all(buf, model_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv[s, e_l] = tokens from source s for my local expert e_l
        xe = jnp.swapaxes(recv, 0, 1).reshape(E_local, n_model * C, d)
        out_e = _expert_ffn(p_local, xe, cfg.mlp_act)     # (E_local, nC, d)
        out_e = jnp.swapaxes(out_e.reshape(E_local, n_model, C, d), 0, 1)
        back = jax.lax.all_to_all(out_e, model_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        back = back.reshape(E_pad, C, d)   # my tokens, expert-major
        y2d = jnp.zeros((T_l, d), jnp.float32)
        for j in range(m.experts_per_token):
            safe_pos = jnp.where(keep[:, j], pos[:, j], C - 1)
            gathered = back[idx[:, j], safe_pos]
            y2d = y2d + jnp.where(keep[:, j, None],
                                  gathered.astype(jnp.float32)
                                  * gates[:, j, None], 0.0)
        y = y2d.reshape(B_l, S_l, d).astype(x_local.dtype)
        return y, aux

    batch_spec = P(data_axes if len(data_axes) > 1 else data_axes[0],
                   model_axis, None)
    espec = P(model_axis, None, None)
    in_specs = (
        {"router": P(None, None), "w_in": espec, "w_gate": espec,
         "w_out": espec},
        batch_spec,
    )
    out_specs = (batch_spec, {"load_balance": P(), "router_z": P()})
    p_moe = {k: p[k] for k in ("router", "w_in", "w_gate", "w_out")}
    if E_pad > E:
        padw = lambda w: jnp.pad(w, ((0, E_pad - E),) + ((0, 0),) * (w.ndim - 1))
        p_moe = dict(p_moe, w_in=padw(p["w_in"]), w_gate=padw(p["w_gate"]),
                     w_out=padw(p["w_out"]))
    y, aux = shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)(p_moe, x)
    if "shared" in p:
        from repro.models.layers import mlp as dense_mlp
        y = y + dense_mlp(p["shared"], x, "silu", True)
    return y, aux


def moe_mlp_ref(p: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Oracle: loop over experts, no capacity drop.  For tests only."""
    m = cfg.moe
    B, S, d = x.shape
    x2d = x.reshape(-1, d)
    gates, idx, _ = _route(p, cfg, x2d)
    y = jnp.zeros_like(x2d, dtype=jnp.float32)
    for e in range(m.num_experts):
        h = jnp.einsum("td,df->tf", x2d, p["w_in"][e])
        if cfg.mlp_act == "silu":
            a = jax.nn.silu(h)
        elif cfg.mlp_act == "relu2":
            a = jnp.square(jax.nn.relu(h))
        else:
            a = jax.nn.gelu(h)
        a = a * jnp.einsum("td,df->tf", x2d, p["w_gate"][e])
        oe = jnp.einsum("tf,fd->td", a, p["w_out"][e]).astype(jnp.float32)
        w = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=1)
        y = y + oe * w[:, None]
    out = y.reshape(B, S, d).astype(x.dtype)
    if "shared" in p:
        from repro.models.layers import mlp as dense_mlp
        out = out + dense_mlp(p["shared"], x, "silu", True)
    return out
