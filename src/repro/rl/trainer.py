"""RL trainer: converts finished BufferEntries into padded update batches
and runs the jitted policy-gradient step.

The importance-sampling denominators come straight from the buffer's cached
per-token behaviour log-probs — the stitched pi_old of partial mode
(paper §3.2): a trajectory interrupted at version v and resumed at v+1 has
its first tokens' ratios computed against v and the rest against v+1.

This module is also the home of the Trainer *protocol* surface
(``Trainer`` / ``make_trainer("sync"|"streaming")`` / ``as_trainer``),
re-exported from the jax-free :mod:`repro.rl.trainer_api` — see that
module for the overlap semantics and the deprecation note on bare
``TrainFn`` callables.

Batch assembly is mesh-aware: under an installed
:func:`repro.distributed.sharding.axis_rules` context the finished batch
is padded to the data-shard count and placed shard-per-device
(:func:`~repro.distributed.sharding.shard_update_batch`); outside any
context it stays a plain host batch.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.core.buffer import BufferEntry
from repro.core.orchestrator import UpdateRequest, UpdateResult
from repro.distributed.sharding import shard_update_batch
from repro.models.model import Model
from repro.rl import advantages as A
from repro.rl.losses import LossConfig, total_loss
# the typed trainer front (protocol + registry + callable shim) lives in
# the jax-free trainer_api module; re-exported here as the public surface
from repro.rl.trainer_api import (CostSpec, StreamingTrainer, SyncTrainer,
                                  TrainOutcome, Trainer, as_trainer,
                                  available_trainers, make_trainer,
                                  register_trainer)
from repro.train.optimizer import AdamWConfig, OptState, adamw_update, init_opt_state


@dataclasses.dataclass
class TrainState:
    params: Dict
    opt_state: OptState
    step: int = 0


RewardFn = Callable[[Sequence[int], object], float]


def entries_to_batch(entries: Sequence[BufferEntry], reward_fn: RewardFn,
                     pad_id: int, max_len: int,
                     advantage_kind: str = "reinforce_pp", *,
                     current_version: Optional[int] = None,
                     ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, float]]:
    """Pad trajectories to a common width and build the update batch.

    tokens = [prompt, generated]; loss_mask covers generated tokens;
    old_logprobs are the buffer's cached behaviour log-probs.  Staleness
    is measured against ``current_version`` — the trainer's policy version
    at update time (threaded from the orchestrator); entries whose prompt
    leaves no room for generated tokens are skipped with a warning (they
    would train on an all-zero loss mask).
    """
    kept, skipped = [], []
    for e in entries:
        (kept if len(e.prompt) < max_len else skipped).append(e)
    if skipped:
        warnings.warn(
            f"entries_to_batch: skipping {len(skipped)} "
            f"entr{'y' if len(skipped) == 1 else 'ies'} with prompt >= "
            f"max_len={max_len} (uids {[e.uid for e in skipped[:8]]}); "
            f"no generated token fits the update window")
    if not kept:
        raise ValueError(
            f"entries_to_batch: all {len(entries)} entries were skipped "
            f"(every prompt >= max_len={max_len})")
    if current_version is None:
        # fallback: newest version seen in the batch (lower bound)
        current_version = max((max(e.versions) for e in kept if e.versions),
                              default=0)
    B = len(kept)
    width = max(e.total_len for e in kept)
    width = min(max_len, (width + 31) // 32 * 32)   # bucket: bounded recompiles
    tokens = np.full((B, width), pad_id, np.int32)
    loss_mask = np.zeros((B, width), np.float32)
    old_lp = np.zeros((B, width), np.float32)
    rewards = np.zeros(B, np.float32)
    staleness = np.zeros(B, np.float32)
    group_ids = np.zeros(B, np.int32)
    # dense group indices: responses sharing a prompt_id form one GRPO
    # group; unrelated prompts never collide
    gid_of: Dict = {}
    for i, e in enumerate(kept):
        seq = (list(e.prompt) + list(e.generated))[:width]
        tokens[i, :len(seq)] = seq
        p = min(len(e.prompt), width)
        g = len(seq) - p
        loss_mask[i, p:p + g] = 1.0
        old_lp[i, p:p + g] = e.logprobs[:g]
        rewards[i] = reward_fn(e.generated, e.meta)
        staleness[i] = e.staleness(current_version)
        pid = getattr(e.meta, "prompt_id", None)
        key = pid if pid is not None else ("uid", e.uid)
        group_ids[i] = gid_of.setdefault(key, len(gid_of))
    lm = jnp.asarray(loss_mask)
    assert float(loss_mask.sum()) > 0, \
        "update batch has no trainable tokens (all-zero loss mask)"
    r = jnp.asarray(rewards)
    if advantage_kind == "reinforce_pp":
        adv = A.reinforce_pp(r, lm)
    elif advantage_kind == "grpo":
        adv = A.grpo(r, jnp.asarray(group_ids), lm,
                     num_groups=int(group_ids.max()) + 1)
    else:
        raise ValueError(advantage_kind)
    batch = {
        "tokens": jnp.asarray(tokens),
        "loss_mask": lm,
        "advantages": adv,
        "old_logprobs": jnp.asarray(old_lp),
    }
    # mesh-aware placement: pads to the data-shard count with inert rows
    # and device_puts shard-per-slice; identity outside an axis_rules
    # context, so host-only callers and token-identity pins are untouched
    batch = shard_update_batch(batch, pad_token=pad_id)
    info = {
        "reward_mean": float(rewards.mean()),
        "reward_std": float(rewards.std()),
        "gen_len_mean": float(np.mean([e.gen_len for e in kept])),
        "solve_rate": float(np.mean(rewards >= 1.2)),
        "staleness_mean": float(staleness.mean()),
        "staleness_max": float(staleness.max()),
        "entries_skipped": float(len(skipped)),
    }
    return batch, info


def make_train_step(model: Model, loss_cfg: LossConfig, opt_cfg: AdamWConfig):
    """Returns jit-able (params, opt_state, batch) -> (params, opt_state,
    metrics).  This is also the function the dry-run lowers at full scale."""

    def loss_fn(params, batch):
        logits, aux = model.forward(params, batch)
        if model.cfg.family == "vlm" and "patch_embeds" in batch:
            # logits cover [patches, tokens]; drop patch positions
            logits = logits[:, model.prefill_extra:]
        return total_loss(logits, aux, batch, loss_cfg)

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        params, opt_state, opt_metrics = adamw_update(
            params, grads, opt_state, opt_cfg)
        metrics.update(opt_metrics)
        return params, opt_state, metrics

    return train_step


class RLTrainer:
    """Host-side wrapper the controller's train_fn hooks into."""

    def __init__(self, model: Model, params, reward_fn: RewardFn,
                 loss_cfg: Optional[LossConfig] = None,
                 opt_cfg: Optional[AdamWConfig] = None,
                 pad_id: int = 0, max_len: int = 512,
                 advantage_kind: str = "reinforce_pp",
                 responses_per_prompt: int = 1):
        # responses_per_prompt is accepted for signature compatibility and
        # run metadata; GRPO grouping is keyed on meta.prompt_id, so the
        # loader-level duplication (GroupedLoader) is what actually
        # produces multi-response groups.
        self.model = model
        self.loss_cfg = loss_cfg or LossConfig()
        self.opt_cfg = opt_cfg or AdamWConfig()
        self.state = TrainState(params, init_opt_state(params, self.opt_cfg))
        self.reward_fn = reward_fn
        self.pad_id = pad_id
        self.max_len = max_len
        self.advantage_kind = advantage_kind
        self.responses_per_prompt = responses_per_prompt
        # params and optimizer state are donated, so the step updates them
        # in place: at Qwen3-0.6B width a step that kept the old state
        # beside the new one would need 16.9 GB for 8x512 tokens on a
        # 15.75 GB TPU v5e (compile-time memory analysis).  Nothing else
        # may hold on to trainer.params() across an update.
        self._step_jit = jax.jit(make_train_step(model, self.loss_cfg,
                                                 self.opt_cfg),
                                 donate_argnums=(0, 1))
        self.history: List[Dict] = []

    def params(self):
        return self.state.params

    def update(self, entries: List[BufferEntry], version: int) -> Dict:
        with trace.span("trainer.update"):
            return self._update(entries, version)

    def _update(self, entries: List[BufferEntry], version: int) -> Dict:
        with trace.span("trainer.batch"):
            batch, info = entries_to_batch(
                entries, self.reward_fn, self.pad_id, self.max_len,
                self.advantage_kind, current_version=version)
        with trace.span("trainer.step"):
            params, opt_state, metrics = self._step_jit(
                self.state.params, self.state.opt_state, batch)
        self.state = TrainState(params, opt_state, self.state.step + 1)
        with trace.span("trainer.readback"):
            rec = {k: float(v) for k, v in metrics.items()}
        rec.update(info)
        rec["version"] = version
        rec["step"] = self.state.step
        self.history.append(rec)
        return rec

    def handle(self, request: UpdateRequest) -> UpdateResult:
        """Typed orchestrator entry point (UpdateRequest -> UpdateResult)."""
        rec = self.update(request.entries, request.version)
        return UpdateResult(metrics=rec)
