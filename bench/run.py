#!/usr/bin/env python3
"""SortedRL benchmark harness: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``).  A run:

1. Set-up (``setup_s``): refuses to run without a TPU (or with fewer chips
   than the cell asks for); builds the RL session's parts through their
   public constructors (weights from the seed, ``RLTrainer``,
   ``SlotEngine`` behind the length adapter, ``RolloutOrchestrator`` under
   the mix's policy); warms every shape the window can reach (each update
   width, prefill bucket and decode block-table width, with synthetic rows
   whose advantages are zero, so no weight moves), recording the tokens
   that all slots serve at every decode width (``Cell.probe``); then
   drives the session through its first ``check_updates`` updates,
   recording what the correctness check needs.
2. Window: drives the same orchestrator, group after group, for exactly
   ``--seconds``, stopping at the edge at a decode-step boundary.  With
   ``--trace 1`` a stretch of the window runs under ``jax.profiler`` and
   the run reports the per-layer metrics instead of the end-to-end ones.
3. Check: reads peak device memory, frees the session, runs the plain
   reference (``bench/reference.py``) over the recorded served tokens and
   first updates and compares (``bench/check.py``).

Metrics are computed by one reader each, ``bench/metrics/<metric>.py``,
from the context this harness gathers.  The last line of stdout is the
result as JSON; the numbers compared, each beside its limit, are the last
lines of stderr and the last key of that JSON.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Dict, List, Optional

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
# JAX's persistent compile cache, at a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, "bench_out", "jax_cache")
# the TPU runtime's logs stay in the checkout too (its default is /tmp)
os.environ.setdefault("TPU_LOG_DIR", os.path.join(ROOT, "bench_out",
                                                  "tpu_logs"))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(RuntimeError):
    pass


class CompileClock:
    """Counts XLA backend compiles and their seconds via jax.monitoring
    (copied from chip_smoke.py), and the persistent cache's hits and
    misses.  A program found in the cache counts as a compile too, with
    the seconds it took to load."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_cache)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def _on_cache(self, event: str, **_) -> None:
        for k in self.cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                self.cache[k] += 1


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_of(bm: Dict, name: str) -> Dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bm: Dict, trace: bool) -> List[Dict]:
    """The end-to-end metrics (trace 0) or the per-layer ones (trace 1)."""
    return bm["per_layer"] if trace else bm["end_to_end"]


def load_reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices_or_refuse(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} devices, the cell asks for {chips}")
    return devs


@dataclasses.dataclass(frozen=True)
class WarmMeta:
    """Meta of a warm-up row: a prompt id no real prompt has, so the
    scripted reward is 0."""
    prompt_id: int


def prefill_widths(mix: Dict) -> List[int]:
    """The prefill width buckets of the mix's fresh prompts."""
    from repro.rollout.engine import next_pow2
    lo, hi = mix["prompt_len"]
    return sorted({min(next_pow2(n - 1), mix["max_total_len"])
                   for n in range(lo, hi + 1)})


# ---------------------------------------------------------------------------
# the session under test
# ---------------------------------------------------------------------------

class Cell:
    """The session's parts, built from their public constructors, plus the
    benchmark's records around them."""

    def __init__(self, c: Dict, mcfg, mix: Dict, seed: int):
        from repro.core.buffer import Mode, StatefulRolloutBuffer
        from repro.core.orchestrator import (RolloutOrchestrator,
                                             SortedRLConfig)
        from repro.core.policy import make_policy
        from repro.models.model import build_model
        from repro.rl.losses import LossConfig
        from repro.rl.trainer import RLTrainer, make_trainer
        from repro.rollout.engine import SlotEngine
        from bench.adapter import LengthAdapter, Spans
        from bench.traffic import Traffic
        from bench.weights import make_weights

        self.c, self.mcfg, self.mix, self.seed = c, mcfg, mix, seed
        M = mix["max_total_len"]
        opt = mix["optimizer"]
        self.model = build_model(mcfg)
        self.traffic = Traffic(mix, seed)
        vocab = self.traffic.vocab
        from repro.train.optimizer import AdamWConfig
        self.trainer = RLTrainer(
            self.model, make_weights(self.model, c, seed),
            self.traffic.reward,
            loss_cfg=LossConfig(clip_eps_low=mix["clip_eps"][0],
                                clip_eps_high=mix["clip_eps"][1]),
            opt_cfg=AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                                eps=opt["eps"],
                                weight_decay=opt["weight_decay"],
                                grad_clip=opt["grad_clip"]),
            pad_id=vocab.pad_id, max_len=M, advantage_kind=mix["advantage"],
            responses_per_prompt=mix["responses_per_prompt"])
        mode = Mode(mix["mode"])
        # no token is an end of sequence (as a serving benchmark sets
        # ignore_eos): the drawn lengths alone end trajectories.  With the
        # task's EOS id, random weights sample it about once in 150k
        # tokens, which would end some seed's trajectory early and change
        # that seed's work.
        self.engine = SlotEngine(
            self.model, self.trainer.params, capacity=mix["slots"],
            max_total_len=M, max_gen_len=M, eos_id=-1,
            pad_id=vocab.pad_id, temperature=mix["temperature"],
            seed=seed & 0x7FFFFFFF,
            kv_retain_across_sync=(mode == Mode.PARTIAL))
        self.lengths: Dict[int, int] = {}
        self.spans = Spans()
        self.adapter = LengthAdapter(self.engine, self.lengths.__getitem__,
                                     self.spans)
        self.buffer = StatefulRolloutBuffer(mode)
        scfg = SortedRLConfig(mode=mode, rollout_batch=mix["slots"],
                              group_size=mix["group_size"],
                              update_batch=mix["update_batch"],
                              max_gen_len=M)
        self.orch = RolloutOrchestrator(
            self.adapter, self.buffer, scfg, make_policy(mix["policy"]),
            make_trainer("sync", fn=self._train_fn))
        # records
        self.updates: List[Dict] = []        # every real update
        self.consumed_at: Dict[int, float] = {}
        self.check_rec: Dict = {"batches": [], "losses": []}
        self.on_update = None                # hook (trace start)

    # -- the train_fn the orchestrator calls ----------------------------------

    def _train_fn(self, req):
        if not self.updates:
            self._record_v0_tokens()
        t0 = time.perf_counter()
        res = self.spans.run("update", self.trainer.handle, req)
        t1 = time.perf_counter()
        rows = [(len(e.prompt), len(e.generated)) for e in req.entries]
        self.updates.append({"t0": t0, "t1": t1, "rows": rows,
                             "staleness_mean": req.staleness_mean,
                             "loss": res.metrics["total_loss"],
                             "skipped": res.metrics.get("entries_skipped",
                                                        0.0)})
        for e in req.entries:
            self.consumed_at[e.uid] = t1
        self._record_check(req, res)
        if self.on_update is not None:
            self.on_update()
        return res

    def _record_v0_tokens(self) -> None:
        """Before the first update every token in the buffer was sampled
        under the starting weights, with KV computed under them too: these
        are the served tokens the reference scores."""
        self.check_rec["v0"] = [
            {"prompt": list(e.prompt), "generated": list(e.generated),
             "logprobs": list(e.logprobs)}
            for e in self.buffer.entries.values() if e.generated]

    def _record_check(self, req, res) -> None:
        from bench.weights import diff_norms, leaf_norms, make_weights
        n = len(self.check_rec["batches"])
        if n >= self.mix["check_updates"]:
            return
        self.check_rec["batches"].append([
            {"prompt": list(e.prompt), "generated": list(e.generated),
             "logprobs": list(e.logprobs),
             "reward": self.traffic.reward(e.generated, e.meta),
             "group": getattr(e.meta, "prompt_id", ("uid", e.uid))}
            for e in req.entries])
        self.check_rec["losses"].append(float(res.metrics["total_loss"]))
        st = self.trainer.state
        if n == 0:
            # the optimizer's first moment after its first real step is
            # (1 - b1) times the clipped gradient (warm-up left it at 0)
            import numpy as np
            b1 = self.trainer.opt_cfg.b1
            self.check_rec["g_first_norms"] = np.asarray(
                leaf_norms(st.opt_state.m)) / (1 - b1)
        if n + 1 == self.mix["check_updates"]:
            import numpy as np
            p0 = make_weights(self.model, self.c, self.seed)
            self.check_rec["dp_norms"] = np.asarray(
                diff_norms(st.params, p0))
            del p0

    # -- driving --------------------------------------------------------------

    def drive(self, stop) -> None:
        """Group after group until ``stop()`` (checked before every decode
        step) ends it; the loop body is RolloutOrchestrator.run_group's."""
        from bench.adapter import Stop
        orch, buf = self.orch, self.buffer
        self.adapter.stop_check = stop
        try:
            while not stop():
                if buf.group_clear():
                    prompts, metas, lens = self.traffic.next_group()
                    uids = buf.load_prompts(prompts, metas)
                    self.lengths.update(zip(uids, lens))
                orch.rollout_until_harvest()
                remaining = len(buf.unconsumed()) - len(buf.done())
                orch.train_ready(final=(remaining == 0))
                buf.check_invariants()
                if buf.group_clear():
                    buf.advance_group()
        except Stop:
            pass

    # -- warm-up --------------------------------------------------------------

    def warm_up(self, clock: "CompileClock") -> Dict[str, Dict]:
        """Run every shape the window can reach once, through the public
        entry points, on synthetic rows.  Update rows all score 0, so each
        GRPO advantage is 0, the gradient is exactly 0 and AdamW leaves the
        weights and moments as they were (its step count advances)."""
        import numpy as np
        from repro.core.buffer import BufferEntry
        from repro.rollout.engine import next_pow2
        mix, eng = self.mix, self.engine
        M, cap, P = mix["max_total_len"], mix["slots"], eng.page_size
        V = self.traffic.vocab
        rng = np.random.default_rng(self.seed & 0xFFFFFFFF)
        uid = iter(range(1 << 40, 1 << 41))

        def toks(n):
            return rng.integers(3, len(V), n).tolist()

        phases: Dict[str, Dict] = {}

        def phase(name, n, since):
            t, c, s = since
            phases[name] = {"shapes": n, "seconds": time.perf_counter() - t,
                            "compiles": clock.count - c,
                            "compile_s": clock.seconds - s}
            return (time.perf_counter(), clock.count, clock.seconds)

        mark = (time.perf_counter(), clock.count, clock.seconds)
        B = mix["update_batch"]
        for j, w in enumerate(range(32, M + 1, 32)):
            # GRPO's eager ops take one shape per number of prompt groups
            # in the batch: the widths cycle through 1..B groups
            rows = []
            for i in range(B):
                gen = toks(max(9, w - 8 * i) - 8)
                meta = WarmMeta(prompt_id=-1 - i % (1 + j % B))
                rows.append(BufferEntry(uid=next(uid), prompt=toks(8),
                                        generated=gen, meta=meta,
                                        logprobs=[-12.0] * len(gen),
                                        versions=[0] * len(gen)))
            self.trainer.update(rows, version=0)
        mark = phase("update_widths", M // 32, mark)

        # prefill buckets: fresh prompts (the only prefills the mixes make:
        # resumes find their pages resident) at every batch bucket
        short = prefill_widths(mix)
        batches = sorted({min(next_pow2(k), cap) for k in range(1, cap + 1)})
        shapes = [(W, kb) for W in short for kb in batches]
        for W, kb in shapes:
            self._poke([BufferEntry(uid=next(uid), prompt=toks(W))
                        for _ in range(kb)])
        mark = phase("prefill_shapes", len(shapes), mark)
        # every decode block-table width, with served tokens recorded
        mark = phase("served_probe", self.probe(rng, uid), mark)
        # copy-on-write page copies: c + 1 entries sharing one prompt whose
        # last page is partial make c copies on their first step
        for c in range(1, cap):
            prompt = toks(P + P // 2)
            self._poke([BufferEntry(uid=next(uid), prompt=list(prompt))
                        for _ in range(c + 1)])
        mark = phase("page_copy_counts", cap - 1, mark)
        # page scatters of fresh prompts as the traffic sends them: k
        # distinct prompts (at most a group's) of two or three pages per
        # submit; the programs differ by batch bucket, width bucket and
        # number of pages
        k_max = min(cap, cap * mix["group_size"]
                    // mix["responses_per_prompt"])
        seen = set()
        for k in range(1, k_max + 1):
            for three in range(0, k + 1):
                key = (min(next_pow2(k), cap), three > 0, 2 * k + three)
                if key in seen:
                    continue
                seen.add(key)
                self._poke([BufferEntry(uid=next(uid),
                                        prompt=toks(2 * P + 3 if j < three
                                                    else P + 3))
                            for j in range(k)])
        phase("page_scatter_counts", len(seen), mark)
        eng.kv.check_invariants()
        assert eng.kv.pool.pages_in_use == 0, eng.kv.pool.pages_in_use
        return phases

    def probe(self, rng, uid) -> int:
        """Serve tokens at every decode block-table width the window's
        contexts reach, under the starting weights, and record them for the
        check (``check_rec["probe"]``): the drive to the first update
        samples only short contexts.

        One round per width ``w`` (pages): every slot is filled, one
        prompt per submit, with a context of more than the next narrower
        width and at most ``w`` pages all through the round (half just
        over the narrower width, half near ``w``), then decoded for 4
        steps; half the slots are interrupted, the rest decode 2 more
        steps, the interrupted half is resubmitted (resumed on its
        resident pages, as partial mode resumes a harvested trajectory)
        and all decode 4 more.  Prompts are random ids of the model's
        vocabulary.  Returns the number of rounds."""
        from repro.core.buffer import BufferEntry
        from repro.rollout.engine import next_pow2
        eng, mix = self.engine, self.mix
        M, cap, P = mix["max_total_len"], mix["slots"], eng.page_size
        pages = -(-M // P)
        V = self.mcfg.vocab_size
        n_steps = (4, 2, 4)
        widths = sorted({min(next_pow2(b), pages)
                         for b in range(1, pages + 1)})
        rows: List[Dict] = []
        rounds, narrower = 0, 0
        for w in widths:
            lo_w, narrower = narrower, w
            if P * w <= mix["prompt_len"][0]:
                continue                # no context of the window is this short
            # the engine ends a row whose context reaches M - 1
            top = min(P * w, M - 2) - sum(n_steps)
            ranges = [(P * lo_w + 1, min(P * lo_w + P, top)),
                      (max(P * (w - 1), P * lo_w) + 1, top)]
            assert all(lo <= hi for lo, hi in ranges), (w, ranges)
            live: Dict[int, Dict] = {}
            for i in range(cap):
                lo, hi = ranges[i % 2]
                n = int(rng.integers(lo, hi + 1))
                e = BufferEntry(uid=next(uid),
                                prompt=rng.integers(3, V, n + 1).tolist())
                eng.submit([e], version=0)
                live[e.uid] = {"prompt": e.prompt, "generated": [],
                               "logprobs": []}

            def decode(k):
                for _ in range(k):
                    for ev in eng.step():
                        r = live[ev.uid]
                        r["generated"].append(ev.token)
                        r["logprobs"].append(ev.logprob)

            decode(n_steps[0])
            held = eng.active_uids()[::2]
            eng.interrupt(held)
            decode(n_steps[1])
            eng.submit([BufferEntry(uid=u, prompt=live[u]["prompt"],
                                    generated=list(live[u]["generated"]))
                        for u in held], version=0)
            decode(n_steps[2])
            for u in live:
                eng.discard_entry(u)
            rows += live.values()
            rounds += 1
        self.check_rec["probe"] = rows
        return rounds

    def _poke(self, entries) -> None:
        eng = self.engine
        eng.submit(entries, version=0)
        eng.step()
        for e in entries:
            eng.discard_entry(e.uid)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def configure_jax() -> None:
    """JAX's persistent compile cache at ``CACHE_DIR`` in the checkout,
    unbounded (an LRU cap smaller than the cell's programs would evict
    each before its next use), for every program: eager page scatters and
    copies compile once per distinct count, and a zero threshold keeps
    those too."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    from repro.compile_cache import enable_compile_cache
    jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def prepare(c: Dict, mcfg, mix: Dict, seed: int, clock: CompileClock):
    """Build, warm up and drive through the checked first updates.
    Returns (the cell, the optimizer's step count before the first real
    update, where the set-up's time and compiles went)."""
    def since(t):
        return {"seconds": time.perf_counter() - t[0],
                "compiles": clock.count - t[1],
                "compile_s": clock.seconds - t[2]}

    t = (time.perf_counter(), clock.count, clock.seconds)
    sess = Cell(c, mcfg, mix, seed)
    info = {"build": dict(since(t), since_process_start=time.perf_counter()
                          - T_START)}
    info["warm_up"] = sess.warm_up(clock)
    first_step = int(sess.trainer.state.opt_state.step)
    t = (time.perf_counter(), clock.count, clock.seconds)
    n_check = mix["check_updates"]
    sess.drive(lambda: len(sess.updates) >= n_check)
    info["drive_to_check"] = since(t)
    return sess, first_step, info


def run(workload: str, seed: int, seconds: float, trace: bool,
        bm: Optional[Dict] = None, require_tpu: bool = True,
        out_dir: Optional[str] = None, cell_override: Optional[Dict] = None,
        persistent_cache: bool = True) -> Dict:
    """One run; returns the result dict (the last stdout line).

    ``cell_override`` ({"config": dict, "mcfg": ModelConfig, "mix": dict})
    replaces the named files, for the CPU tests at tiny sizes."""
    bm = bm or load_benchmark()
    cell = cell_of(bm, workload)
    devs = devices_or_refuse(cell["chips"], require_tpu)
    if persistent_cache:
        configure_jax()
    from bench import peaks
    from bench.model_config import load_model_config
    from bench.traffic import load_mix
    if cell_override:
        c, mcfg, mix = (cell_override["config"], cell_override["mcfg"],
                        cell_override["mix"])
    else:
        c, mcfg = load_model_config(cell["config"])
        mix = load_mix(cell["traffic"])
    peak = peaks.lookup(devs[0].device_kind) if require_tpu else None
    clock = CompileClock()
    sess, first_step, setup_info = prepare(c, mcfg, mix, seed, clock)
    setup_s = time.perf_counter() - T_START
    compiles_setup = clock.count

    # -- window ---------------------------------------------------------------
    from bench import flops as F
    n_int0 = len(sess.orch.metrics.intervals)
    n_upd0 = len(sess.updates)
    work0 = work_counters(sess.adapter)
    uid0 = max(sess.buffer.entries) if sess.buffer.entries else -1
    spans0 = (dict(sess.spans.total), dict(sess.spans.count))
    tracer = Tracer(sess, seconds, out_dir) if trace else None
    t0 = time.perf_counter()
    t_end = t0 + seconds
    if tracer:
        sess.on_update = tracer.on_update

        def stop():
            now = time.perf_counter()
            tracer.poll(now)
            return now >= t_end
    else:
        def stop():
            return time.perf_counter() >= t_end
    sess.drive(stop)
    t1 = time.perf_counter()
    if tracer:
        tracer.stop()
    window_s = t1 - t0
    compiles_window = clock.count - compiles_setup

    # -- gather the context the readers use -----------------------------------
    upd = [u for u in sess.updates[n_upd0:] if u["t1"] <= t1]
    lat = [sess.consumed_at[u] - sess.adapter.first_admit[u]
           for u in sess.consumed_at
           if t0 <= sess.consumed_at[u] <= t1]
    work = {k: v - work0[k] for k, v in work_counters(sess.adapter).items()}
    work["update_rows"] = [r for u in upd for r in u["rows"]]
    ctx = {
        "setup_s": setup_s, "window_s": window_s,
        "tokens": work["decode_tokens"],
        "latencies": lat, "updates": upd,
        "intervals": sess.orch.metrics.intervals[n_int0:],
        "span_s": {k: v - spans0[0].get(k, 0.0)
                   for k, v in sess.spans.total.items()},
        "span_n": {k: v - spans0[1].get(k, 0)
                   for k, v in sess.spans.count.items()},
        "capacity": mix["slots"],
        "config": c, "mix": mix, "peak": peak,
        "flops": F.counts(c), "trace": tracer.summary if tracer else None,
    }
    ctx["window_flops"] = F.window_flops(c, work)
    attempted = sum(1 for t in sess.adapter.first_admit.values()
                    if t0 <= t <= t1)
    failed = sum(1 for u in upd if not _finite(u["loss"]))
    failed += int(sum(u["skipped"] for u in upd))
    mem = devs[0].memory_stats() or {}
    memory_peak = int(mem.get("peak_bytes_in_use", 0))

    metrics = {}
    for m in metrics_for(bm, trace):
        val = load_reader(m["name"])(ctx)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}

    info = dict(setup_info, optimizer_step_before_check=first_step,
                compiles_setup=compiles_setup,
                compiles_window=compiles_window,
                compile_s_total=clock.seconds,
                compile_cache=dict(clock.cache),
                latency_samples=len(lat), updates_in_window=len(upd),
                tokens_in_window=ctx["tokens"], uid_at_window_start=uid0)
    print(json.dumps({"info": info}), flush=True)

    # -- correctness ----------------------------------------------------------
    rec = sess.check_rec
    width = mix["max_total_len"]
    trace_summary = tracer.summary if tracer else None
    del sess, ctx, tracer
    gc.collect()
    from bench import check
    t_check = time.perf_counter()
    numbers, leaves = check.compare(c, mcfg, mix, seed, rec, first_step,
                                    width)
    print(json.dumps({"check_s": time.perf_counter() - t_check,
                      "check_leaves": leaves}), flush=True)
    limits = check.load_limits(workload) if cell_override is None \
        else cell_override.get("limits")
    verdict = check.judge(numbers, limits)
    correct = all(v["value"] <= v["limit"] for v in verdict.values())

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace_summary is not None:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
        result["breakdown"] = {
            "device_ops": trace_summary["device_ops"][:10],
            "idle_gaps": trace_summary["idle_gaps"][:10]}
    return result, verdict


def work_counters(a) -> Dict[str, float]:
    return {"decode_tokens": a.tokens_out, "decode_ctx": a.decode_ctx,
            "prefill_tokens": a.prefill_tokens,
            "prefill_ctx": a.prefill_ctx}


def _finite(x) -> bool:
    import math
    return isinstance(x, float) and math.isfinite(x)


class Tracer:
    """Runs a stretch of the window under jax.profiler: from the end of
    the first update after a quarter of the window to the end of the next
    update, so the stretch holds one whole update and the decode steps
    between the two.  Without an update by 60% of the window it starts
    there, and it stops at the window's edge at the latest."""

    def __init__(self, sess: Cell, seconds: float, out_dir: Optional[str]):
        self.sess, self.seconds = sess, seconds
        self.dir = out_dir or os.path.join(ROOT, "bench_out", "trace")
        self.t_window = time.perf_counter()
        self.t0 = self.t1 = None
        self.summary = None
        self.marks: Dict = {}

    def _mark(self) -> Dict:
        s = self.sess
        return {"t": time.perf_counter(), "n_updates": len(s.updates),
                "n_steps": len(s.adapter.step_contexts)}

    def on_update(self) -> None:
        if self.t0 is None:
            if time.perf_counter() - self.t_window >= 0.25 * self.seconds:
                self._start()
        elif self.t1 is None:
            self._stop()

    def poll(self, now: float) -> None:
        if self.t0 is None and now - self.t_window >= 0.6 * self.seconds:
            self._start()

    def _start(self) -> None:
        import jax
        import shutil
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self.sess.spans.annotate = True
        self.sess.adapter.record_steps = True
        jax.profiler.start_trace(self.dir)
        self.marks["start"] = self._mark()
        self.t0 = self.marks["start"]["t"]

    def _stop(self) -> None:
        import jax
        self.marks["stop"] = self._mark()
        self.t1 = self.marks["stop"]["t"]
        jax.profiler.stop_trace()
        self.sess.spans.annotate = False
        self.sess.adapter.record_steps = False

    def stop(self) -> None:
        if self.t0 is None:
            return
        if self.t1 is None:
            self._stop()
        from bench import trace_reduce
        s = self.sess
        a, b = self.marks["start"], self.marks["stop"]
        self.summary = trace_reduce.reduce_dir(self.dir)
        self.summary["host"] = {
            "updates": s.updates[a["n_updates"]:b["n_updates"]],
            "step_contexts": s.adapter.step_contexts[a["n_steps"]:
                                                     b["n_steps"]],
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, verdict = run(args.workload, args.seed, args.seconds,
                              bool(args.trace))
    except NoChip as e:
        print(f"bench/run.py: {e}; refusing to run", file=sys.stderr)
        return 2
    result["check"] = verdict
    for name, v in verdict.items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
