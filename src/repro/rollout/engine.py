"""Slot-based JAX rollout engine.

TPU adaptation of the paper's SGLang/CUDA-graph setup: a *fixed* slot count
means the jitted ``decode_step`` has one static shape — the XLA analogue of
graph capture.  Oversubscription (the controller refilling slots every
step) keeps the engine at its saturation batch; early termination frees
slots at harvest boundaries.  Inactive slots decode garbage that is masked
out — exactly the padding waste the bubble ratio (Eq. 4) measures.

Weight sync is O(1): the engine reads params through a callback, so the
trainer's latest state is always visible (colocated / stage-fused setup).

Memory model
------------
For standard right-padding attention caches (keys ``{"k", "v"}``) the
engine is **paged**: physical KV storage is a pool of fixed-size pages
``(L, num_pages, page_size, Kh, D)`` and each sequence owns a refcounted
page table (:mod:`repro.core.kv_cache`).  This buys what a dense
``capacity x max_total_len`` cache cannot:

* **GRPO prefix sharing** — entries submitted with an identical prefill
  prefix (group members share one prompt) prefill ONCE; the other G-1
  members map the same prefix pages.  Divergence is copy-on-write at the
  page written by decode.
* **Resume without re-prefill** — interrupted sequences keep their pages
  resident, so a scavenged ``partial``-mode entry (or the prompt of an
  on-policy re-roll) resumes by remapping pages instead of re-running
  prefill.
The decode step works on the pool in place: each layer's attention reads
that layer's live pages through the block tables (bucketed to a
power-of-two table width) — the Pallas ``paged_decode_attention`` on a
TPU, its jnp reference on the CPU — and the new token's K/V rows go
straight into their pages after the layer scan.  The pool (and an int8
pool's scales) is donated to the decode program, so no step copies it.

Families with exotic cache layouts (ssm/hybrid state, local/global ring
buffers, cross-attention) fall back to the dense layout (``paged=False``).

Hot-path notes
--------------
* ``step()`` stays loop-free on the host for slot bookkeeping: EOS/budget
  masking, event construction, and slot retirement are numpy array ops
  over the :class:`SlotTable`.  Page bookkeeping (COW planning, block
  tables, committed-token appends) is O(active) python per step inside
  :class:`~repro.core.kv_cache.PagedKVCache` — same order as event
  construction, and small next to the device step.
* Prefill shapes are bucketed — width to the next power of two (clamped
  to ``max_total_len``) and batch to the next power of two (clamped to
  ``capacity``) — so ``_prefill_cache`` holds at most
  O(log max_total_len · log capacity) compiled functions.  The paged
  decode compiles one variant per power-of-two block-table width,
  bounded by O(log pages_per_seq).
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import trace
from repro.core.buffer import BufferEntry
from repro.core.engine_api import SlotTable, StepEvent
from repro.core.kv_cache import PagedKVCache, PoolExhausted
from repro.models.model import Model

# per-family cache batch-axis maps (see Model cache layouts)
CACHE_BATCH_AXIS = {
    "k": 1, "v": 1, "k_local": 1, "v_local": 1, "k_global": 1, "v_global": 1,
    "k_x": 1, "v_x": 1,
    "ssm_main": 2, "conv_x_main": 2, "conv_bc_main": 2, "ssm_tail": 1,
    "conv_x_tail": 1, "conv_bc_tail": 1,
    "attn_k": 1, "attn_v": 1,
    "mlstm_C": 1, "mlstm_n": 1, "mlstm_conv": 1,
    "slstm_c": 1, "slstm_n": 1, "slstm_h": 1, "slstm_m": 1,
}

DEFAULT_PAGE_SIZE = 16


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (n >= 1)."""
    return 1 << (n - 1).bit_length()


def cache_put(cache: Dict[str, jnp.ndarray], sub: Dict[str, jnp.ndarray],
              slots: np.ndarray) -> Dict[str, jnp.ndarray]:
    """Write per-slot sub-cache into the engine cache at `slots`.

    The sub-cache batch may be padded past ``len(slots)`` (batch-bucketed
    prefill); only the first ``len(slots)`` rows are real and written.
    """
    out = {}
    k = len(slots)
    for name, arr in cache.items():
        ax = CACHE_BATCH_AXIS[name]
        sl = sub[name]
        if sl.shape[ax] != k:
            sl = jax.lax.slice_in_dim(sl, 0, k, axis=ax)
        idx = (slice(None),) * ax + (slots,)
        out[name] = arr.at[idx].set(sl.astype(arr.dtype))
    return out


def _put_slots(pool: jnp.ndarray, rows: jnp.ndarray,
               *starts: jnp.ndarray) -> jnp.ndarray:
    """``rows[:, b]`` of each slot b into ``pool[:, starts[0][b],
    starts[1][b], ...]``, one ``dynamic_update_slice`` a slot.  Each stays
    in place in the pool's own layout; a scatter there would have XLA lay
    out a narrow-head pool (64 lanes of head_dim, whose device layout puts
    the page axis minor) afresh and back, two copies of the pool a step.
    Slots sharing a page (the garbage page) write in slot order."""
    for b in range(rows.shape[1]):
        at = [0] * pool.ndim
        for ax, s in enumerate(starts, start=1):
            at[ax] = s[b]
        pool = jax.lax.dynamic_update_slice(pool, rows[:, b:b + 1], at)
    return pool


def _named(scope: str, fn: Callable) -> Callable:
    """``fn`` with its ops under ``jax.named_scope(scope)``; a jitted
    program keeps ``fn``'s name."""
    @functools.wraps(fn)
    def scoped(*args):
        with jax.named_scope(scope):
            return fn(*args)
    return scoped


def supports_paging(model: Model) -> bool:
    """Paged layout needs right padding, a plain {k, v} cache and a decode
    step that reads a page pool."""
    if model.padding_side != "right" or model.decode_paged is None:
        return False
    shapes = jax.eval_shape(lambda: model.init_cache(1, 1))
    return set(shapes) == {"k", "v"}


class SlotEngine:
    def __init__(self, model: Model, params_fn: Callable[[], Dict],
                 capacity: int, max_total_len: int, max_gen_len: int,
                 eos_id: int, pad_id: int = 0, temperature: float = 1.0,
                 seed: int = 0, paged: Optional[bool] = None,
                 page_size: int = DEFAULT_PAGE_SIZE,
                 num_pages: Optional[int] = None,
                 kv_retain_across_sync: bool = True,
                 packed_prefill: bool = False,
                 fused_sampling: bool = False,
                 kv_quant: Optional[str] = None,
                 device: Optional[jax.Device] = None):
        """``device``: where the KV pool lives (None: JAX's default
        device).  The pool is committed there, so every jitted step runs
        there too; ``params_fn`` must return weights on the same device."""
        self.model = model
        self.params_fn = params_fn
        self.capacity = capacity
        self.max_total_len = max_total_len
        self.max_gen_len = max_gen_len
        self.eos_id = eos_id
        self.pad_id = pad_id
        self.temperature = temperature
        self._key = jax.random.PRNGKey(seed)
        self._t0 = time.monotonic()
        self.version = 0

        if paged is None:
            paged = supports_paging(model)
        elif paged:
            assert supports_paging(model), \
                "paged KV cache requires right padding and a {k, v} cache"
        self.paged = paged
        assert kv_quant in (None, "int8"), kv_quant
        self.kv_quant = kv_quant
        if kv_quant:
            assert paged, "kv_quant requires the paged layout"
        self.packed_prefill = packed_prefill
        if packed_prefill:
            assert paged and model.prefill_packed is not None \
                and model.prefill_extra == 0, \
                "packed_prefill requires a paged engine, a family with " \
                "segment-masked prefill, and no stub frontend rows"
        self.fused_sampling = fused_sampling
        if fused_sampling:
            assert paged, "fused_sampling requires the paged layout"
        self.prefill_launches = 0       # one per prefill kernel launch
        self.slots = SlotTable(capacity)
        with jax.default_device(device):
            if paged:
                self.page_size = page_size
                self._pages_per_seq = -(-max_total_len // page_size)
                # default: dense-equivalent capacity + COW headroom + garbage
                self.num_pages = num_pages or (
                    capacity * self._pages_per_seq + capacity + 1)
                self.cache = model.init_cache(self.num_pages, page_size)
                if kv_quant == "int8":
                    # quantized page pool: int8 storage + one f32 scale per
                    # (layer, page) plane — ~4x (f32) / ~2x (bf16) the token
                    # capacity at equal bytes
                    nl = self.cache["k"].shape[0]
                    self.cache = {name: jnp.zeros(arr.shape, jnp.int8)
                                  for name, arr in self.cache.items()}
                    self.kv_scales = {
                        "k": jnp.ones((nl, self.num_pages), jnp.float32),
                        "v": jnp.ones((nl, self.num_pages), jnp.float32)}
                else:
                    self.kv_scales = {}
                # retain=True keeps resident/shared KV across weight syncs
                # (PipelineRL/APRIL approximation, counted in stale_kv_reuses);
                # retain=False restores dense fresh-prefill-after-update
                # semantics — use it for on-policy re-rolls (see rl/session.py)
                self.kv = PagedKVCache(self.num_pages, page_size,
                                       extra_rows=model.prefill_extra,
                                       retain_across_sync=kv_retain_across_sync)
                self._paged_decode_cache: Dict[Tuple, Callable] = {}
            else:
                self.kv_scales = {}
                self.cache = model.init_cache(capacity, max_total_len)
                self.kv = None
                self._decode_jit = jax.jit(self._decode_fn)
        if device is not None:
            self.cache, self.kv_scales = jax.device_put(
                (self.cache, self.kv_scales), device)
        # int8 and fp cache configs must not collide on a (width, batch)
        # bucket — the KV dtype is part of every compile-cache key
        self._kv_dtype_key = kv_quant or jnp.dtype(
            model.cfg.compute_dtype).name
        self._prefill_cache: Dict[Tuple, Callable] = {}

    # -- time ---------------------------------------------------------------

    @property
    def clock(self) -> float:
        return time.monotonic() - self._t0

    # -- slot queries ---------------------------------------------------------

    def free_slots(self) -> int:
        return self.slots.free_count()

    def active_uids(self) -> List[int]:
        return self.slots.active_uids()

    def sync_weights(self, version: int) -> None:
        if self.paged:
            self.kv.sync_version(version)
        self.version = version   # params_fn always reads the latest state

    def cache_stats(self) -> Optional[Dict[str, float]]:
        """Page-pool gauges + prefix-sharing counters (None when dense)."""
        if not self.paged:
            return None
        d = self.kv.stats_dict()
        d["prefill_launches"] = float(self.prefill_launches)
        return d

    # -- submit: batched prefill of new entries into free slots ---------------

    def submit(self, entries: Sequence[BufferEntry], version: int) -> None:
        if not entries:
            return
        with trace.span("engine.submit"):
            slots = self.slots.allocate(len(entries))
            seqs = [list(e.prompt) + list(e.generated) for e in entries]
            # prefill everything but the last token; it is fed on the next
            # step
            pre = [s[:-1] for s in seqs]
            if self.paged:
                self._submit_paged(entries, slots, seqs, pre)
            else:
                self._submit_dense(entries, slots, seqs, pre)

    def _submit_dense(self, entries, slots, seqs, pre) -> None:
        k = len(entries)
        params = self.params_fn()
        width = self._bucket_width(max(1, max(len(p) for p in pre)))
        kb = self._bucket_batch(k)
        toks = np.full((kb, width), self.pad_id, np.int32)
        plens = np.zeros(kb, np.int32)
        for i, p in enumerate(pre):
            plens[i] = len(p)
            if self.model.padding_side == "right":
                toks[i, :len(p)] = p
            else:
                toks[i, width - len(p):] = p

        batch = {"tokens": jnp.asarray(toks), "prompt_lens": jnp.asarray(plens)}
        self._add_stub_inputs(batch, kb)
        sub_cache = self.model.init_cache(kb, self.max_total_len)
        _, sub_cache = self._prefill(params, batch, sub_cache, width, kb)
        self.cache = cache_put(self.cache, sub_cache, slots)

        t = self.slots
        t.uid[slots] = [e.uid for e in entries]
        t.active[slots] = True
        t.next_token[slots] = [s[-1] for s in seqs]
        if self.model.padding_side == "right":
            t.kv_len[slots] = plens[:k] + self.model.prefill_extra
            t.kv_start[slots] = 0
        else:
            t.kv_len[slots] = width
            t.kv_start[slots] = width - plens[:k]
        t.gen_count[slots] = [len(e.generated) for e in entries]
        t.gen_budget[slots] = self.max_gen_len

    def _submit_paged(self, entries, slots, seqs, pre) -> None:
        """Prefill only unique, non-resident prefixes; map everyone else
        onto existing pages (prefix sharing / resume-without-reprefill)."""
        kv = self.kv
        leaders: List[int] = []
        followers: List[Tuple[int, int]] = []   # (idx, leader idx)
        key_leader: Dict[Tuple[int, ...], int] = {}
        for i, e in enumerate(entries):
            key = tuple(pre[i])
            if kv.try_resume(e.uid, key):
                continue                        # pages still resident
            donor = kv.find_donor(key)
            if donor is not None:
                kv.share(e.uid, donor, key)     # cross-batch sharing
                continue
            li = key_leader.get(key)
            if li is None:
                key_leader[key] = i
                leaders.append(i)
            else:
                followers.append((i, li))       # in-batch sharing
        if leaders:
            self._prefill_to_pages([entries[i] for i in leaders],
                                   [pre[i] for i in leaders])
        for i, li in followers:
            kv.share(entries[i].uid, entries[li].uid, tuple(pre[i]))

        t = self.slots
        extra = self.model.prefill_extra
        t.uid[slots] = [e.uid for e in entries]
        t.active[slots] = True
        t.next_token[slots] = [s[-1] for s in seqs]
        t.kv_len[slots] = [len(p) + extra for p in pre]
        t.kv_start[slots] = 0
        t.gen_count[slots] = [len(e.generated) for e in entries]
        t.gen_budget[slots] = self.max_gen_len

    def _prefill_to_pages(self, entries, pres) -> None:
        """Run prefill over the unique prefixes and scatter the resulting
        KV rows into freshly allocated pages.  Default path: one bucketed
        dense launch per batch; with ``packed_prefill`` the prefixes are
        concatenated into rows (segment-masked attention), so one launch
        covers the whole fill wave without per-prompt padding waste."""
        if self.packed_prefill:
            self._prefill_to_pages_packed(entries, pres)
            return
        params = self.params_fn()
        P = self.page_size
        extra = self.model.prefill_extra
        width = self._bucket_width(max(1, max(len(p) for p in pres)))
        kb = self._bucket_batch(len(entries))
        cache_len = -(-(width + extra) // P) * P
        toks = np.full((kb, width), self.pad_id, np.int32)
        plens = np.zeros(kb, np.int32)
        for i, p in enumerate(pres):
            plens[i] = len(p)
            toks[i, :len(p)] = p                # paged => right padding
        batch = {"tokens": jnp.asarray(toks), "prompt_lens": jnp.asarray(plens)}
        self._add_stub_inputs(batch, kb)
        sub_cache = self.model.init_cache(kb, cache_len)
        _, sub_cache = self._prefill(params, batch, sub_cache, width, kb)

        rows, blks, phys = [], [], []
        for i, (e, p) in enumerate(zip(entries, pres)):
            table = self.kv.register_prefill(e.uid, tuple(p))
            for j, page in enumerate(table):
                rows.append(i)
                blks.append(j)
                phys.append(page)
        with trace.span("engine.page_scatter"):
            self._scatter_pages(sub_cache, np.asarray(rows), np.asarray(blks),
                                np.asarray(phys))

    def _prefill_to_pages_packed(self, entries, pres) -> None:
        """Packed ragged prefill: bin-pack page-aligned prefix spans into
        a few rows of concatenated segments and run ONE segment-masked
        launch for the whole wave.

        Each prefix occupies ``ceil(len/P)*P`` columns (page-aligned so
        its KV pages are whole row blocks); first-fit-decreasing packing
        into ``max_total_len``-column rows, then the usual pow2 width /
        batch bucketing on the packed shape.  Attention is masked by
        segment id and positions restart per segment, so the KV written
        for each prefix is identical to a solo prefill of that prefix.
        """
        params = self.params_fn()
        P = self.page_size
        span = [-(-max(len(p), 1) // P) * P for p in pres]
        order = sorted(range(len(pres)), key=lambda i: -span[i])
        row_of = [0] * len(pres)
        offset = [0] * len(pres)
        fill: List[int] = []                    # columns used per row
        for i in order:
            for r, used in enumerate(fill):
                if used + span[i] <= self.max_total_len:
                    row_of[i], offset[i] = r, used
                    fill[r] = used + span[i]
                    break
            else:
                row_of[i], offset[i] = len(fill), 0
                fill.append(span[i])
        width = self._bucket_width(max(fill))
        kb = self._bucket_batch(len(fill))
        cache_len = -(-width // P) * P

        toks = np.full((kb, width), self.pad_id, np.int32)
        seg = np.full((kb, width), -1, np.int32)
        pos = np.zeros((kb, width), np.int32)
        plens = np.zeros(kb, np.int32)
        for i, p in enumerate(pres):
            r, o = row_of[i], offset[i]
            toks[r, o:o + len(p)] = p
            seg[r, o:o + span[i]] = i           # pad tail shares the segment
            pos[r, o:o + span[i]] = np.arange(span[i])
            plens[r] = max(plens[r], o + len(p))
        batch = {"tokens": jnp.asarray(toks),
                 "prompt_lens": jnp.asarray(plens),
                 "seg_ids": jnp.asarray(seg),
                 "positions": jnp.asarray(pos)}
        sub_cache = self.model.init_cache(kb, cache_len)
        key = ("packed", width, kb, self._kv_dtype_key)
        fn = self._prefill_cache.get(key)
        if fn is None:
            fn = jax.jit(_named("prefill", self.model.prefill_packed))
            self._prefill_cache[key] = fn
        with trace.span("engine.prefill"):
            _, sub_cache = fn(params, batch, sub_cache)
        self.prefill_launches += 1

        rows, blks, phys = [], [], []
        for i, (e, p) in enumerate(zip(entries, pres)):
            table = self.kv.register_prefill(e.uid, tuple(p))
            for j, page in enumerate(table):
                rows.append(row_of[i])
                blks.append(offset[i] // P + j)
                phys.append(page)
        with trace.span("engine.page_scatter"):
            self._scatter_pages(sub_cache, np.asarray(rows), np.asarray(blks),
                                np.asarray(phys))

    def _scatter_pages(self, sub_cache, rows, blks, phys) -> None:
        """Scatter prefilled KV page blocks into the pool at ``phys``
        (quantizing per page when the pool is int8)."""
        P = self.page_size
        cache = dict(self.cache)
        scales = dict(self.kv_scales)
        for name in ("k", "v"):
            sub = sub_cache[name]               # (L, kb, cache_len, Kh, D)
            nl, nb_, ns = sub.shape[:3]
            blocks = sub.reshape(nl, nb_, ns // P, P, *sub.shape[3:])
            sel = blocks[:, rows, blks]         # (L, n_pages, P, Kh, D)
            if self.kv_quant == "int8":
                sel = sel.astype(jnp.float32)
                amax = jnp.max(jnp.abs(sel), axis=(2, 3, 4))
                s = jnp.maximum(amax, 1e-8) / 127.0
                q = jnp.clip(jnp.round(sel / s[:, :, None, None, None]),
                             -127, 127).astype(jnp.int8)
                cache[name] = cache[name].at[:, phys].set(q)
                scales[name] = scales[name].at[:, phys].set(s)
            else:
                cache[name] = cache[name].at[:, phys].set(
                    sel.astype(cache[name].dtype))
        self.cache = cache
        self.kv_scales = scales

    def _add_stub_inputs(self, batch: Dict, k: int) -> None:
        cfg = self.model.cfg
        if cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (k, cfg.num_stub_positions, cfg.d_model), cfg.compute_dtype)
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros(
                (k, cfg.num_stub_positions, cfg.d_model), cfg.compute_dtype)

    # -- prefill shape bucketing ----------------------------------------------

    def _bucket_width(self, width: int) -> int:
        assert width <= self.max_total_len, (width, self.max_total_len)
        if self.model.padding_side == "right":
            # padded positions beyond prompt_lens are masked via kv_len, so
            # inflating the width is free
            return min(next_pow2(width), self.max_total_len)
        # left padding: valid tokens END at the bucketed width, so kv_len =
        # width and every padded column eats generation headroom out of the
        # fixed cache.  Bucket only while the full gen budget still fits;
        # past that, fall back to the exact width (seed behaviour).
        safe = self.max_total_len - self.max_gen_len - 1
        return max(width, min(next_pow2(width), max(safe, 1)))

    def _bucket_batch(self, k: int) -> int:
        return min(next_pow2(k), self.capacity)

    def _prefill(self, params, batch, cache, width, kb):
        key = (width, kb, self._kv_dtype_key)
        fn = self._prefill_cache.get(key)
        if fn is None:
            fn = jax.jit(_named("prefill", self.model.prefill))
            self._prefill_cache[key] = fn
        self.prefill_launches += 1
        with trace.span("engine.prefill"):
            return fn(params, batch, cache)

    # -- decode ---------------------------------------------------------------

    def _sample(self, logits, key):
        logits = logits.astype(jnp.float32)
        if self.temperature > 0:
            sampled = jax.random.categorical(key, logits / self.temperature,
                                             axis=-1)
        else:
            sampled = jnp.argmax(logits, axis=-1)
        logprobs = jax.nn.log_softmax(logits, axis=-1)
        lp = jnp.take_along_axis(logprobs, sampled[:, None], axis=1)[:, 0]
        return sampled.astype(jnp.int32), lp

    def _decode_fn(self, params, token, cache, kv_len, kv_start, key):
        logits, cache = self.model.decode_step(params, token, cache, kv_len,
                                               kv_start=kv_start)
        sampled, lp = self._sample(logits, key)
        return sampled, lp, cache

    def _fused_greedy(self, params, hidden):
        """Fused greedy LM head: the token and its logprob come straight
        out of max / logsumexp reductions over the logits — no (B, V)
        log-softmax materialisation, no gather, and no variadic argmax
        reduce (the dominant cost of the two-pass path on CPU; on TPU the
        Pallas drop-in ``kernels.ops.fused_sample`` additionally streams
        the matmul so the (B, V) logits never round-trip through HBM).
        First-index-at-max reproduces argmax's tie-break, so tokens are
        bit-identical to the two-pass path."""
        cfg = self.model.cfg
        w = (params["embed"].T if cfg.tie_embeddings
             else params["lm_head"]).astype(cfg.compute_dtype)
        logits = jnp.einsum("bd,dv->bv", hidden, w).astype(jnp.float32)
        if cfg.logit_softcap > 0:
            logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
        v = logits.shape[1]
        m = jnp.max(logits, axis=-1)
        iota = jnp.arange(v)
        idx = jnp.min(jnp.where(logits == m[:, None], iota[None, :], v),
                      axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        return idx.astype(jnp.int32), m - lse

    def _paged_decode_fn(self, params, token, cache, scales, bt, kv_len,
                         key):
        """One decode step over the page pool, in place.

        The model's paged decode reads each layer's pages through the
        block tables and attends to the new token too; the new token's
        K/V rows are then written at ``kv_len`` of each slot's write page
        (``_write_kv``).  Host-side COW (``prepare_step``) guarantees write
        pages are exclusively owned, so the writes never collide except on
        the shared garbage page of inactive slots.  ``cache`` and
        ``scales`` are donated by ``_paged_decode``: the returned pool
        replaces them.

        Its ops sit under the named scopes ``decode/model`` (the layer
        scan, with ``attend`` around each layer's attention),
        ``decode/sample`` and ``decode/write_kv``.
        """
        fused = self.fused_sampling and self.temperature == 0
        with jax.named_scope("decode"):
            with jax.named_scope("model"):
                out, (k_new, v_new) = self.model.decode_paged(
                    params, token, cache, scales, bt, kv_len,
                    return_hidden=fused)
            with jax.named_scope("sample"):
                sampled, lp = (self._fused_greedy(params, out) if fused
                               else self._sample(out, key))
            with jax.named_scope("write_kv"):
                cache, scales = self._write_kv(cache, scales, k_new, v_new,
                                               bt, kv_len)
        return sampled, lp, cache, scales

    def _write_kv(self, cache, scales, k_new, v_new, bt, kv_len):
        """The new token's rows (L, B, Kh, D) into row ``kv_len % P`` of
        each slot's page ``bt[b, kv_len // P]``.  An int8 page is
        dequantized, given its row and requantized with a monotone scale
        (``max(old, amax / 127)``), so a page whose amax did not grow
        keeps its old cells exactly."""
        P = self.page_size
        B = bt.shape[0]
        phys = jnp.take_along_axis(bt, (kv_len // P)[:, None], axis=1)[:, 0]
        off = kv_len % P
        cache, scales = dict(cache), dict(scales)
        for name, new in (("k", k_new), ("v", v_new)):
            if self.kv_quant != "int8":
                cache[name] = _put_slots(
                    cache[name], new.astype(cache[name].dtype)[:, :, None],
                    phys, off)
                continue
            old = scales[name][:, phys]                     # (L, B)
            page = (cache[name][:, phys].astype(jnp.float32)
                    * old[..., None, None, None]).astype(new.dtype)
            page = page.at[:, jnp.arange(B), off].set(new).astype(
                jnp.float32)
            s = jnp.maximum(old, jnp.max(jnp.abs(page), axis=(2, 3, 4))
                            / 127.0)
            cache[name] = _put_slots(cache[name], jnp.clip(
                jnp.round(page / s[..., None, None, None]), -127,
                127).astype(jnp.int8), phys)
            scales[name] = _put_slots(scales[name], s, phys)
        return cache, scales

    def _decode_program(self, width: int) -> Callable:
        """The jitted decode program for a block-table width, with the
        pool and its scales donated."""
        fused = self.fused_sampling and self.temperature == 0
        cache_key = (width, self._kv_dtype_key, fused)
        fn = self._paged_decode_cache.get(cache_key)
        if fn is None:
            fn = jax.jit(self._paged_decode_fn, donate_argnums=(2, 3))
            self._paged_decode_cache[cache_key] = fn
        return fn

    def _paged_decode(self, params, token, cache, bt, kv_len, key):
        return self._decode_program(bt.shape[1])(params, token, cache,
                                                 self.kv_scales, bt, kv_len,
                                                 key)

    def _copy_pages(self, copies: List[Tuple[int, int]]) -> None:
        """Apply host-planned copy-on-write page copies on device (scale
        planes travel with their pages on a quantized pool)."""
        src = np.asarray([s for s, _ in copies])
        dst = np.asarray([d for _, d in copies])
        self.cache = {name: arr.at[:, dst].set(arr[:, src])
                      for name, arr in self.cache.items()}
        if self.kv_quant:
            self.kv_scales = {name: arr.at[:, dst].set(arr[:, src])
                              for name, arr in self.kv_scales.items()}

    def step(self) -> List[StepEvent]:
        """One decode step of every active slot.  Its spans split it in
        order: ``prepare`` (copy-on-write, block table, key, uploads),
        ``launch`` (dispatch of the decode program), ``wait`` (the sampled
        tokens reaching the host) and ``events`` (page append, slot
        bookkeeping, page release, the events)."""
        t = self.slots
        act = t.active_indices()
        if act.size == 0:
            return []
        with trace.span("engine.step"):
            with trace.span("engine.step.prepare"):
                params = self.params_fn()
                self._key, sub = jax.random.split(self._key)
                kv_len = jnp.asarray(
                    np.where(t.active, t.kv_len, 0).astype(np.int32))
                token = jnp.asarray(t.next_token)
                if self.paged:
                    uids_act = t.uid[act].tolist()
                    copies = self.kv.prepare_step(uids_act,
                                                  t.kv_len[act].tolist())
                    if copies:
                        self._copy_pages(copies)
                    nb = min(next_pow2(max(1, self.kv.max_blocks(uids_act))),
                             self._pages_per_seq)
                    bt = jnp.asarray(self.kv.block_table(t.uid.tolist(), nb))
                else:
                    kv_start = jnp.asarray(t.kv_start)
            with trace.span("engine.step.launch"):
                if self.paged:
                    sampled, lp, self.cache, self.kv_scales = \
                        self._paged_decode(params, token, self.cache, bt,
                                           kv_len, sub)
                else:
                    sampled, lp, self.cache = self._decode_jit(
                        params, token, self.cache, kv_len, kv_start, sub)
            with trace.span("engine.step.wait"):
                sampled = np.asarray(sampled)
                lp = np.asarray(lp)
            with trace.span("engine.step.events"):
                if self.paged:
                    self.kv.append_tokens(uids_act,
                                          t.next_token[act].tolist())
                return self._step_events(act, sampled, lp)

    def _step_events(self, act, sampled, lp) -> List[StepEvent]:
        t = self.slots
        # vectorized bookkeeping over the active slots (ascending order)
        t.kv_len[act] += 1
        t.gen_count[act] += 1
        toks = sampled[act]
        eos = toks == self.eos_id
        over = ((t.gen_count[act] >= t.gen_budget[act])
                | (t.kv_len[act] >= self.max_total_len - 1))
        done = eos | over
        reasons = np.where(eos, "eos", np.where(over, "length", None))

        uids = t.uid[act].tolist()          # read before batched release
        if self.paged:
            self.kv.release_many(t.uid[act[done]].tolist())
        t.release(act[done])
        cont = act[~done]
        t.next_token[cont] = toks[~done]

        return [StepEvent(uid=u, token=tk, logprob=l, done=d, finish_reason=r)
                for u, tk, l, d, r in zip(uids, toks.tolist(), lp[act].tolist(),
                                          done.tolist(), reasons.tolist())]

    def interrupt(self, uids: Optional[Sequence[int]] = None) -> List[int]:
        with trace.span("engine.interrupt"):
            sel = self.slots.select(uids)
            out = [int(u) for u in self.slots.uid[sel]]
            self.slots.release(sel)
            if self.paged:
                self.kv.deactivate_many(out)   # keep pages resident
            return out

    def shutdown(self) -> None:
        """Fence the engine (killed or scaled-down replica): release
        every slot and purge the page pool, so the fleet holds no live
        references to this replica.  Counters survive — the work done
        before the fence was real."""
        self.slots.release(self.slots.active_indices())
        if self.paged:
            self.kv.purge()

    # -- migration capability (EngineGroup work stealing / tail packing) ------
    #
    # A migrated entry carries its resident KV across page pools (span
    # copy on device via the page tables), so a stolen or drain-packed
    # sequence resumes on the destination replica with ZERO re-prefill.
    # The three-call shape (export -> import -> discard) keeps the donor
    # copy intact until the importer has accepted, so a failed import
    # (full pool, no free slot) falls back without losing anything.

    def export_entry(self, uid: int) -> Optional[Dict]:
        """Snapshot an in-flight slot or a resident uid — page table
        bookkeeping from :meth:`PagedKVCache.export_pages` plus the
        physical KV rows pulled off the donor pool.  None when the engine
        cannot migrate (dense layout, or no trace of the uid)."""
        if not self.paged or uid not in self.kv.tables:
            return None
        ex = self.kv.export_pages(uid)
        handle = {
            "engine": "slot", "uid": uid, "active": ex.active, "kv": ex,
            "kv_quant": self.kv_quant,
            # span copy: the donor's physical rows for ex.pages (host
            # round-trip; a multi-host deployment would DMA these)
            "pages_k": np.asarray(self.cache["k"][:, ex.pages]),
            "pages_v": np.asarray(self.cache["v"][:, ex.pages]),
        }
        if self.kv_quant:
            handle["scales_k"] = np.asarray(self.kv_scales["k"][:, ex.pages])
            handle["scales_v"] = np.asarray(self.kv_scales["v"][:, ex.pages])
        if ex.active:
            sel = np.flatnonzero((self.slots.uid == uid) & self.slots.active)
            assert sel.size == 1, (uid, sel)
            i = int(sel[0])
            t = self.slots
            handle["slot"] = {"next_token": int(t.next_token[i]),
                              "kv_len": int(t.kv_len[i]),
                              "kv_start": int(t.kv_start[i]),
                              "gen_count": int(t.gen_count[i]),
                              "gen_budget": int(t.gen_budget[i])}
        return handle

    def import_entry(self, handle: Dict) -> bool:
        """Land a migrated entry with its KV: fresh pages from this pool
        (``import_pages``), donor rows copied in, and — for an active
        entry — a slot transplanted verbatim so greedy decode continues
        token-identically.  Returns False (engine unchanged) when it
        cannot accept: dense layout, stale KV under strict sync, no free
        slot, or an exhausted pool."""
        if handle.get("engine") != "slot" or not self.paged:
            return False
        if handle.get("kv_quant") != self.kv_quant:
            return False    # int8 and fp pools do not mix page bytes
        ex = handle["kv"]
        if not self.kv.retain_across_sync and ex.version != self.kv.version:
            return False    # strict sync: pre-sync KV must not cross pools
        if ex.active and self.free_slots() <= 0:
            return False
        try:
            pages = self.kv.import_pages(ex)
        except PoolExhausted:
            return False
        cache = dict(self.cache)
        for name, rows in (("k", handle["pages_k"]), ("v", handle["pages_v"])):
            cache[name] = cache[name].at[:, pages].set(
                jnp.asarray(rows, cache[name].dtype))
        self.cache = cache
        if self.kv_quant:
            sc = dict(self.kv_scales)
            for name, rows in (("k", handle["scales_k"]),
                               ("v", handle["scales_v"])):
                sc[name] = sc[name].at[:, pages].set(jnp.asarray(rows))
            self.kv_scales = sc
        if ex.active:
            s = handle["slot"]
            slot = self.slots.allocate(1)
            t = self.slots
            t.uid[slot] = ex.uid
            t.active[slot] = True
            t.next_token[slot] = s["next_token"]
            t.kv_len[slot] = s["kv_len"]
            t.kv_start[slot] = s["kv_start"]
            t.gen_count[slot] = s["gen_count"]
            t.gen_budget[slot] = s["gen_budget"]
        return True

    def discard_entry(self, uid: int) -> None:
        """Drop every local trace of a migrated-away uid (slot + pages)."""
        sel = self.slots.select([uid])
        if sel.size:
            self.slots.release(sel)
        if self.paged:
            self.kv.release_seq(uid)
