#!/usr/bin/env python3
"""Chip smoke: the RL session at Qwen3-0.6B width on a TPU.

    python3 chip_smoke.py              # one chip: session, then kernels
    python3 chip_smoke.py --chips 4    # four chips: the replica phase only

Session phase: ``RLSession.from_config`` builds the published Qwen3-0.6B
configuration (bf16, 151936-entry vocab, random weights from the seed,
remat on) and runs at least two RL updates in partial mode under the
``sorted`` policy, GRPO pairs sharing a prompt, so interrupt/resume and
paged prefix sharing run as well.  It checks that every update's loss is
finite, that the trainer stepped at least twice, that the first update's
teacher-forced logprobs agree with those the engine recorded while
sampling, that tokens were generated, that the page pool holds no
leaked pages and that the decode program aliases the donated pool (its
``memory_analysis`` is printed with the phase's facts).

Kernel phase: the five Pallas kernels, compiled for the chip, against
their pure-jnp oracles at Qwen3-0.6B head widths, depth and vocab; the
paged decode attention through ``ops.paged_attention``, the entry point
the decode program calls.

Replica phase: the session's replica factory puts greedy SlotEngine
replica i of an EngineGroup on local device i.  Each uid's token stream
must equal that of one SlotEngine on the default device fed the same
batch shapes, and each replica's page pool and weights must live on its
own device.

Earlier lines of stdout are informational (wall and compile seconds,
tokens, peak device bytes, and from the program's span recorder
``repro.core.trace``: compiles by the span that caused them, seconds and
self seconds by span, and the trajectories' time running and waiting for
their update), not benchmark metrics.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.core import trace  # noqa: E402
from repro.core.buffer import BufferEntry, Mode  # noqa: E402
from repro.rl.session import SessionConfig  # noqa: E402


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def qwen3_0_6b():
    from repro.configs.base import get_config
    return get_config("qwen3-0.6b").replace(remat=True)


def session_config(model_cfg) -> SessionConfig:
    """The session phase's run: 8 slots x 512 tokens, 16 trajectories
    (8 prompts x 2 responses), updates of 8 rows.  A random-init model
    almost never emits EOS, so each trajectory runs until its prompt plus
    generation fills ``max_total_len``; prompts of different lengths
    finish at different steps, and the harvest at 8 done interrupts the
    rest, which resume from their resident pages."""
    return SessionConfig(
        task="logic", policy="sorted", mode=Mode.PARTIAL, model=model_cfg,
        sft_steps=0, rollout_batch=8, group_size=2, responses_per_prompt=2,
        advantage_kind="grpo", update_batch=8, n_groups=1,
        max_total_len=512, max_gen_len=512, temperature=1.0, seed=0,
        eval_every=1 << 30, eval_size=2)


def _p90(values) -> float | None:
    values = list(values)
    return statistics.quantiles(values, n=10)[8] if len(values) >= 2 else None


def trace_facts(rec: trace.Recorder, got: trace.Taken) -> dict:
    """What the program's recorder saw: backend compiles (and the span
    open during each); per span name its entries, seconds and self seconds
    (less its child spans); and of the trajectories an update consumed,
    how many were harvested and resumed, with the p90 seconds from first
    running to done and from done to the consuming update."""
    own = {}
    for s, ns in zip(got.spans, trace.self_ns(got.spans)):
        own[s.name] = own.get(s.name, 0.0) + ns * 1e-9
    consumed = [s for s in got.stamps.values() if "consume" in s]
    return {"compile_s": sum(c[1] for c in got.compiles.values()),
            "compiles": sum(c[0] for c in got.compiles.values()),
            "compiles_by_span": {k: c[0] for k, c in got.compiles.items()},
            "span_s": {k: [rec.count[k], rec.total[k], own.get(k, 0.0)]
                       for k in rec.total},
            "trajectories": {
                "consumed": len(consumed),
                "harvested": sum("harvest" in s for s in consumed),
                "run_p90_s": _p90(trace.between(got.stamps, "running",
                                                "done").values()),
                "done_wait_p90_s": _p90(trace.between(got.stamps, "done",
                                                      "consume").values())}}


def memory_stats() -> dict:
    """Device 0's allocator counters (peak_bytes_in_use among them)."""
    import jax
    return jax.devices()[0].memory_stats() or {"peak_bytes_in_use":
                                               "not reported"}


def session_phase(model_cfg) -> dict:
    from repro.rl.session import RLSession
    t0 = time.perf_counter()
    session = RLSession.from_config(session_config(model_cfg))
    built = time.perf_counter() - t0
    out = session.run()
    ran = time.perf_counter() - t0 - built

    history = session.trainer.history
    losses = [h["total_loss"] for h in history]
    check(len(history) >= 2, f"{len(history)} updates, want >= 2")
    check(all(math.isfinite(h[k]) for h in history
              for k in ("total_loss", "grad_norm")),
          f"non-finite update: {history}")
    check(session.trainer.state.step >= 2,
          f"trainer.state.step={session.trainer.state.step}")
    tokens = out["rollout_metrics"]["tokens_generated"]
    check(tokens > 0, "no tokens generated")
    eng = session.engine
    check(not eng.active_uids(), "slots still active after the run")
    eng.kv.check_invariants()
    check(eng.kv.pool.pages_in_use == 0,
          f"{eng.kv.pool.pages_in_use} pages leaked")
    st = eng.cache_stats()
    check(st["shared_prefills"] > 0, "no GRPO prefix was shared")
    check(st["resumed_without_prefill"] > 0,
          "no interrupted trajectory resumed from resident pages")
    # the first update trains on tokens sampled under the very weights it
    # starts from, so the trainer's teacher-forced logprobs must reproduce
    # the engine's recorded ones up to bf16 rounding: ratio 1, no clipping
    first = {k: history[0][k] for k in ("ratio_mean", "kl_to_old",
                                        "clip_frac")}
    check(history[0]["version"] == 0 and abs(first["kl_to_old"]) <= 0.05
          and first["clip_frac"] <= 0.05,
          f"engine logprobs disagree with the trainer's: {first}")
    step_s = sorted(dt for running, dt in session.metrics.intervals
                    if running > 0)
    decode = decode_program_memory(eng)
    check(decode["alias_bytes"] >= decode["pool_bytes"],
          f"the decode program does not alias the page pool: {decode}")
    return {"phase": "session", "model": model_cfg.name,
            "build_s": built, "run_s": ran, "updates": len(history),
            "losses": losses, "first_update": first,
            "tokens_generated": tokens,
            "engine_steps": len(step_s),
            "engine_step_s_median": step_s[len(step_s) // 2],
            "shared_prefills": st["shared_prefills"],
            "resumed_without_prefill": st["resumed_without_prefill"],
            "harvests": out["rollout_metrics"]["harvests"],
            "decode_program": decode}


def decode_program_memory(eng) -> dict:
    """``memory_analysis`` of the engine's decode program at its widest
    block table: the bytes it aliases (the donated pool and scales) and
    its temporaries, beside the pool's own bytes."""
    import jax
    import jax.numpy as jnp
    nb, B = eng._pages_per_seq, eng.capacity

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)
    m = eng._decode_program(nb).lower(
        eng.params_fn(), i32(B), eng.cache, eng.kv_scales, i32(B, nb),
        i32(B), eng._key).compile().memory_analysis()
    return {"width": nb,
            "pool_bytes": sum(a.nbytes for a in jax.tree.leaves(
                (eng.cache, eng.kv_scales))),
            "alias_bytes": m.alias_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes}


def kernel_phase(model_cfg, slots: int = 8, seq: int = 512,
                 page: int = 16) -> dict:
    """Each Pallas kernel through its jitted wrapper in ``kernels/ops.py``
    against its pure-jnp oracle in ``kernels/ref.py``, at the model's head
    widths and vocab.  Inputs are bf16; the oracles take them upcast to
    f32 at full matmul precision, and the bounds are the bf16 ones of
    ``tests/test_kernels.py``.  The compiled program must hold a Mosaic
    call exactly when the backend is not the CPU: no kernel is
    interpreted on a chip.  Returns each kernel's max abs error."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import default_interpret, ops, ref
    H, Kh = model_cfg.num_heads, model_cfg.num_kv_heads
    D, Dm = model_cfg.resolved_head_dim, model_cfg.d_model
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))

    def normal(*shape, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale).astype(
            jnp.bfloat16)

    nb = seq // page
    n_pages = slots * nb + 1
    L = model_cfg.num_layers
    q = normal(slots, H, D)
    kv_len = jax.random.randint(next(keys), (slots,), 1, seq)
    bt = jax.random.permutation(next(keys), n_pages)[:slots * nb].reshape(
        slots, nb).astype(jnp.int32)
    # the engine's layered pool, read at its last layer, with the new
    # token's rows
    pool = (L, n_pages, page, Kh, D)
    k_pages, v_pages = normal(*pool), normal(*pool)
    layer = jnp.int32(L - 1)
    k_new, v_new = normal(slots, Kh, D), normal(slots, Kh, D)
    k8, k_scale = ref.quantize_pages_ref(k_pages.reshape(-1, *pool[2:]))
    v8, v_scale = ref.quantize_pages_ref(v_pages.reshape(-1, *pool[2:]))
    k8, v8 = k8.reshape(pool), v8.reshape(pool)
    k_scale, v_scale = k_scale.reshape(pool[:2]), v_scale.reshape(pool[:2])
    fq = normal(2, seq, H, D)
    fk, fv = normal(2, seq, Kh, D), normal(2, seq, Kh, D)
    # row 0 packs two prompts and a pad tail; row 1 is one prompt
    seg = np.full((2, seq), -1, np.int32)
    seg[0, :seq * 3 // 8], seg[0, seq * 3 // 8:seq * 7 // 8] = 0, 1
    seg[1] = 0
    x, w = normal(slots, Dm), normal(Dm, model_cfg.vocab_size,
                                     scale=Dm ** -0.5)

    def with_scales(attend):
        return lambda q, k, v, ks, vs, *rest: attend(
            q, k, v, *rest, k_scales=ks, v_scales=vs)

    # the paged attention is checked through the entry point the decode
    # program calls (the kernel on the chip at 128-lane head widths)
    cases = {
        "ragged_decode_attention": (
            ops.ragged_decode_attention, ref.ragged_decode_attention_ref,
            (q, normal(slots, seq, Kh, D), normal(slots, seq, Kh, D),
             kv_len), 2e-2),
        "paged_decode_attention": (
            jax.jit(ops.paged_attention), ref.paged_decode_attention_ref,
            (q, k_pages, v_pages, bt, kv_len, layer, k_new, v_new), 2e-2),
        "paged_decode_attention_int8": (
            jax.jit(with_scales(ops.paged_attention)),
            with_scales(ref.paged_decode_attention_ref),
            (q, k8, v8, k_scale, v_scale, bt, kv_len, layer, k_new, v_new),
            2e-2),
        "flash_attention": (
            ops.flash_attention,
            lambda q, k, v, s: ref.flash_attention_ref(q, k, v, seg_ids=s),
            (fq, fk, fv, jnp.asarray(seg)), 3e-2),
        "fused_sample": (ops.fused_sample, ref.fused_sample_ref, (x, w),
                         2e-2),
    }
    errors = {}
    for name, (kernel, oracle, args, tol) in cases.items():
        compiled = kernel.lower(*args).compile()
        check(("tpu_custom_call" in compiled.as_text())
              == (not default_interpret()),
              f"{name}: compiled on the wrong path for "
              f"{jax.default_backend()}")
        got = compiled(*args)
        with jax.default_matmul_precision("highest"):
            want = oracle(*[a.astype(jnp.float32)
                            if a.dtype == jnp.bfloat16 else a
                            for a in args])
        if name == "fused_sample":            # (max logit, index, lse)
            check(np.array_equal(got[1], want[1]),
                  f"{name}: greedy token differs from argmax")
            got, want = jnp.stack([got[0], got[2]]), jnp.stack([want[0],
                                                                want[2]])
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        if name == "flash_attention":         # pad rows are garbage
            got, want = got[seg >= 0], want[seg >= 0]
        check(np.allclose(got, want, atol=tol, rtol=tol),
              f"{name}: max error {np.abs(got - want).max()} over {tol}")
        errors[name] = float(np.abs(got - want).max())
    return {"phase": "kernels", "model": model_cfg.name,
            "max_abs_error": errors}


def _drain(engine, entries) -> dict:
    """Feed `entries` into free slots in order and decode until all
    finish; returns each uid's token stream."""
    toks = {e.uid: [] for e in entries}
    queue = list(entries)
    while queue or engine.active_uids():
        n = min(engine.free_slots(), len(queue))
        if n:
            engine.submit(queue[:n], version=0)
            queue = queue[n:]
        for ev in engine.step():
            toks[ev.uid].append(ev.token)
    return toks


def replica_phase(model_cfg, n: int, per_replica: int = 2,
                  prompt_len: int = 24) -> dict:
    import jax
    from repro.rl.session import RLSession
    from repro.rollout.engine import SlotEngine
    from repro.rollout.group import EngineGroup
    devices = jax.local_devices()
    check(len(devices) >= n, f"{len(devices)} local devices, want {n}")
    cfg = SessionConfig(task="logic", policy="sorted", model=model_cfg,
                        sft_steps=0, num_replicas=n,
                        rollout_batch=n * per_replica, temperature=0.0,
                        max_total_len=128, max_gen_len=32, eval_size=1)
    t0 = time.perf_counter()
    session = RLSession.from_config(cfg)
    group = session.engine
    check(isinstance(group, EngineGroup) and len(group.replicas) == n,
          "session did not build an EngineGroup of n replicas")
    for i, r in enumerate(group.replicas):
        for name, pool in r.cache.items():
            check(pool.devices() == {devices[i]},
                  f"replica {i} pool {name!r} on {pool.devices()}")
        check(all(p.devices() == {devices[i]}
                  for p in jax.tree.leaves(r.params_fn())),
              f"replica {i} weights are not on its device")

    # equal-length, distinct prompts: every replica step and the single
    # engine's steps see identical shapes (pairs of equal-length slots)
    vocab = session.vocab
    prompts = [[vocab.bos_id] + [6 + (7 * u + 3 * j) % (len(vocab) - 6)
                                 for j in range(prompt_len - 1)]
               for u in range(n * per_replica)]
    entries = [BufferEntry(uid=u, prompt=list(p))
               for u, p in enumerate(prompts)]
    got = _drain(group, entries)
    single = SlotEngine(session.model, session.trainer.params,
                        capacity=per_replica, max_total_len=cfg.max_total_len,
                        max_gen_len=cfg.max_gen_len, eos_id=vocab.eos_id,
                        pad_id=vocab.pad_id, temperature=0.0)
    want = _drain(single, [BufferEntry(uid=u, prompt=list(p))
                           for u, p in enumerate(prompts)])
    check(got == want, "EngineGroup token streams differ from one engine: "
          + str({u: (got[u], want[u]) for u in got if got[u] != want[u]}))
    return {"phase": "replicas", "model": model_cfg.name, "replicas": n,
            "wall_s": time.perf_counter() - t0,
            "tokens_generated": sum(len(t) for t in got.values()),
            "devices": [str(d) for d in devices[:n]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the replica phase, one replica per "
                         "chip")
    args = ap.parse_args(argv)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform!r}); "
              "refusing to run", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    print(json.dumps({"compile_cache": enable_compile_cache()}))
    rec = trace.enable()
    try:
        if args.chips == 4:
            print(json.dumps(replica_phase(qwen3_0_6b(), 4)))
        else:
            print(json.dumps(session_phase(qwen3_0_6b())))
            print(json.dumps(kernel_phase(qwen3_0_6b())))
    except SmokeFailure as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    finally:
        trace.disable()
    print(json.dumps(dict(trace_facts(rec, rec.take()),
                          memory=memory_stats())))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
