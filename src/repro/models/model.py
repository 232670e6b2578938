"""Unified model interface: ``build_model(cfg)`` returns a :class:`Model`
bundle of pure functions shared by the trainer, the rollout engine, and the
dry-run launcher.

Batch dict conventions
----------------------
* train / scoring : {"tokens": (B, S) i32, ...}  (+ "patch_embeds" for vlm,
  "frames" for audio — the stub frontends per the assignment carve-out)
* prefill         : {"tokens": (B, S) i32, "prompt_lens": (B,) i32, ...}
* decode          : token (B,) i32, cache pytree, kv_len (B,) i32
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import hybrid as HY
from repro.models import moe as MOE
from repro.models import transformer as TF
from repro.models import whisper as WH
from repro.models import xlstm as XL


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init_params: Callable          # (key) -> params
    forward: Callable              # (params, batch) -> (logits, aux)
    init_cache: Callable           # (batch_size, max_len) -> cache
    prefill: Callable              # (params, batch, cache) -> (logits, cache)
    decode_step: Callable          # (params, token, cache, kv_len, **kw) -> (logits, cache)
    padding_side: str              # "right" (attention) | "left" (ssm/hybrid)
    prefill_extra: int = 0         # cache rows prepended by the stub frontend
    # packed ragged prefill: several prompts concatenated per row with
    # segment-offset tables (batch adds "seg_ids"/"positions").  None for
    # families without segment-masked attention support.
    prefill_packed: Any = None     # (params, batch, cache) -> (logits, cache)
    # decode on a layered page pool read in place (families whose cache is
    # plain {k, v} with full attention); None for the others.
    # (params, token, pages, scales, block_tables, kv_len, return_hidden)
    #   -> (logits or hidden, (k_new, v_new))
    decode_paged: Any = None


def _moe_mlp_fn(cfg: ModelConfig, ep_mesh=None, data_axes=("data",)):
    if ep_mesh is not None:
        def fn(p, x):
            return MOE.moe_mlp_ep(p, cfg, x, ep_mesh, data_axes=data_axes)
    else:
        def fn(p, x):
            return MOE.moe_mlp_dense(p, cfg, x)
    return fn


def build_model(cfg: ModelConfig, ep_mesh=None, data_axes=("data",)) -> Model:
    fam = cfg.family

    if fam in ("dense", "moe", "vlm"):
        mlp_fn = _moe_mlp_fn(cfg, ep_mesh, data_axes) if fam == "moe" else None
        mlp_init = ((lambda k: MOE.init_moe_mlp(k, cfg, cfg.param_dtype))
                    if fam == "moe" else None)

        def init_params(key):
            return TF.init_params(cfg, key, mlp_init=mlp_init)

        def forward(params, batch):
            if fam == "vlm" and "patch_embeds" in batch:
                tok_e = TF.embed_tokens(params, cfg, batch["tokens"])
                pe = batch["patch_embeds"].astype(tok_e.dtype)
                x = jnp.concatenate([pe, tok_e], axis=1)
                return TF.forward(params, cfg, embeds=x, mlp_fn=mlp_fn)
            return TF.forward(params, cfg, batch["tokens"], mlp_fn=mlp_fn)

        def init_cache(batch_size, max_len):
            return TF.init_cache(cfg, batch_size, max_len)

        def prefill(params, batch, cache):
            embeds = None
            if fam == "vlm" and "patch_embeds" in batch:
                tok_e = TF.embed_tokens(params, cfg, batch["tokens"])
                pe = batch["patch_embeds"].astype(tok_e.dtype)
                embeds = jnp.concatenate([pe, tok_e], axis=1)
            return TF.prefill(params, cfg, batch["tokens"], cache,
                              batch["prompt_lens"], mlp_fn=mlp_fn,
                              embeds=embeds)

        def decode_step(params, token, cache, kv_len, **kw):
            return TF.decode(params, cfg, token, cache, kv_len, mlp_fn=mlp_fn,
                             return_hidden=kw.get("return_hidden", False))

        decode_paged = None
        if TF.pattern_len(cfg) == 1 and not cfg.attn.sliding_window:
            def decode_paged(params, token, pages, scales, block_tables,
                             kv_len, return_hidden=False):
                return TF.decode_step_paged(
                    params, cfg, token, pages, scales, block_tables, kv_len,
                    mlp_fn=mlp_fn, return_hidden=return_hidden)

        prefill_packed = None
        if fam != "vlm":
            # vlm prepends stub patch rows per prompt — incompatible with
            # the packed layout's contiguous-segment assumption
            def prefill_packed(params, batch, cache):
                return TF.prefill(params, cfg, batch["tokens"], cache,
                                  batch["prompt_lens"], mlp_fn=mlp_fn,
                                  seg_ids=batch["seg_ids"],
                                  positions=batch["positions"])

        return Model(cfg, init_params, forward, init_cache, prefill,
                     decode_step, padding_side="right",
                     prefill_extra=(cfg.num_stub_positions
                                    if fam == "vlm" else 0),
                     prefill_packed=prefill_packed,
                     decode_paged=decode_paged)

    if fam == "hybrid":
        def forward(params, batch):
            return HY.forward(params, cfg, batch["tokens"]), dict(TF.ZERO_AUX)

        def prefill(params, batch, cache):
            return HY.prefill(params, cfg, batch["tokens"], cache,
                              batch["prompt_lens"])

        def decode_step(params, token, cache, kv_len, **kw):
            return HY.decode_step(params, cfg, token, cache, kv_len,
                                  kv_start=kw.get("kv_start"))

        return Model(cfg, lambda key: HY.init_params(cfg, key), forward,
                     lambda b, m: HY.init_cache(cfg, b, m), prefill,
                     decode_step, padding_side="left")

    if fam == "ssm":
        def forward(params, batch):
            return XL.forward(params, cfg, batch["tokens"]), dict(TF.ZERO_AUX)

        def prefill(params, batch, cache):
            return XL.prefill(params, cfg, batch["tokens"], cache,
                              batch["prompt_lens"])

        def decode_step(params, token, cache, kv_len, **kw):
            return XL.decode_step(params, cfg, token, cache, kv_len)

        return Model(cfg, lambda key: XL.init_params(cfg, key), forward,
                     lambda b, m: XL.init_cache(cfg, b, m), prefill,
                     decode_step, padding_side="left")

    if fam == "audio":
        def forward(params, batch):
            return (WH.forward(params, cfg, batch["tokens"], batch["frames"]),
                    dict(TF.ZERO_AUX))

        def prefill(params, batch, cache):
            return WH.prefill(params, cfg, batch["tokens"], cache,
                              batch["prompt_lens"],
                              frames=batch.get("frames"))

        def decode_step(params, token, cache, kv_len, **kw):
            return WH.decode_step(params, cfg, token, cache, kv_len)

        return Model(cfg, lambda key: WH.init_params(cfg, key), forward,
                     lambda b, m: WH.init_cache(cfg, b, m), prefill,
                     decode_step, padding_side="right")

    raise ValueError(f"unknown family {fam!r}")


# ---------------------------------------------------------------------------
# input_specs: ShapeDtypeStruct stand-ins for the dry-run (no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, seq_len: int, batch: int, kind: str
                ) -> Dict[str, Any]:
    """Returns the batch pytree as ShapeDtypeStructs for jit(...).lower().

    train  : RL update-step inputs (tokens, loss_mask, advantages, old_logprobs)
    prefill: prompt batch
    decode : one-token step inputs (token, kv_len) — the KV cache spec is
             built separately via ``cache_specs``.
    """
    sds = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    if kind == "train":
        batch_specs = {
            "tokens": sds((batch, seq_len), i32),
            "loss_mask": sds((batch, seq_len), f32),
            "advantages": sds((batch, seq_len), f32),
            "old_logprobs": sds((batch, seq_len), f32),
        }
    elif kind == "prefill":
        batch_specs = {
            "tokens": sds((batch, seq_len), i32),
            "prompt_lens": sds((batch,), i32),
        }
    elif kind == "decode":
        batch_specs = {
            "token": sds((batch,), i32),
            "kv_len": sds((batch,), i32),
        }
    else:
        raise ValueError(kind)
    if cfg.family == "vlm" and kind != "decode":
        batch_specs["patch_embeds"] = sds(
            (batch, cfg.num_stub_positions, cfg.d_model), jnp.bfloat16)
    if cfg.family == "audio" and kind != "decode":
        batch_specs["frames"] = sds(
            (batch, cfg.num_stub_positions, cfg.d_model), jnp.bfloat16)
    return batch_specs


def cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    """Cache pytree as ShapeDtypeStructs (eval_shape — no allocation)."""
    model = build_model(cfg)
    return jax.eval_shape(lambda: model.init_cache(batch, max_len))
