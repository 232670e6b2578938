"""Batched serving example: a small LM behind the slot engine answering a
stream of Knights & Knaves prompts with continuous batching — the same
engine the RL controller drives, used inference-only.

  PYTHONPATH=src python examples/serve_batch.py --requests 24 --slots 8
"""
import argparse
import time

import jax

from repro.compile_cache import enable_compile_cache
from repro.core.buffer import BufferEntry
from repro.data import logic
from repro.models.model import build_model
from repro.rollout.engine import SlotEngine
from repro.train.loop import tiny_lm_config


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-gen", type=int, default=16)
    args = ap.parse_args()

    vocab = logic.VOCAB
    model = build_model(tiny_lm_config(len(vocab), d_model=96, layers=2))
    params = model.init_params(jax.random.PRNGKey(0))
    engine = SlotEngine(model, lambda: params, capacity=args.slots,
                        max_total_len=128, max_gen_len=args.max_gen,
                        eos_id=vocab.eos_id, pad_id=vocab.pad_id,
                        temperature=0.0)

    gen = logic.LogicTaskGenerator(seed=1)
    prompts, metas = gen.batch(args.requests)
    queue = [BufferEntry(uid=i, prompt=p, meta=m)
             for i, (p, m) in enumerate(zip(prompts, metas))]
    outputs = {e.uid: [] for e in queue}
    t0 = time.monotonic()
    steps = 0
    while queue or engine.active_uids():
        free = engine.free_slots()
        if free and queue:
            engine.submit(queue[:free], 0)   # continuous batching
            queue = queue[free:]
        for ev in engine.step():
            outputs[ev.uid].append(ev.token)
        steps += 1
    dt = time.monotonic() - t0
    total = sum(len(v) for v in outputs.values())
    print(f"served {args.requests} requests, {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s, {steps} engine steps)")
    for uid in list(outputs)[:3]:
        print(f"  req{uid}: {' '.join(vocab.decode(outputs[uid]))}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
