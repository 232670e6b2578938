#!/usr/bin/env python3
"""Compile a cell's largest programs for a described TPU v5e, without the
chip, and print what ``memory_analysis`` says each needs:

* the donated update step at ``update_batch`` x ``max_total_len``;
* the paged decode step at the full block-table width, over the pool
  (donated, as the engine jits it);
* the reference's per-row gradient step at ``max_total_len``, beside the
  float32 weights, moments and gradient sum it keeps.

    JAX_PLATFORMS=cpu python3 bench/fit.py --workload <name>
    JAX_PLATFORMS=cpu python3 bench/fit.py --config <c> --traffic <mix>

Nothing runs; a compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", help="a cell of BENCHMARK.json")
    ap.add_argument("--config", help="or a configuration ...")
    ap.add_argument("--traffic", help="... and a traffic mix")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.models.model import build_model
    from repro.rl.losses import LossConfig
    from repro.rl.trainer import make_train_step
    from repro.rollout.engine import SlotEngine
    from repro.train.optimizer import AdamWConfig, init_opt_state
    from bench import run as R
    from bench.model_config import load_model_config
    from bench.reference import Reference
    from bench.traffic import load_mix

    jax.config.update("jax_enable_compilation_cache", False)
    if args.workload:
        cell = R.cell_of(R.load_benchmark(), args.workload)
        args.config, args.traffic = cell["config"], cell["traffic"]
    c, mcfg = load_model_config(args.config)
    mix = load_mix(args.traffic)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    def report(name, compiled, resident):
        m = compiled.memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes - m.alias_size_in_bytes + resident)
        print(json.dumps({"program": name,
                          "argument_bytes": m.argument_size_in_bytes,
                          "output_bytes": m.output_size_in_bytes,
                          "temp_bytes": m.temp_size_in_bytes,
                          "alias_bytes": m.alias_size_in_bytes,
                          "resident_bytes_beside": resident,
                          "total_bytes": need}), flush=True)

    model = build_model(mcfg)
    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    M, S, B = mix["max_total_len"], mix["slots"], mix["update_batch"]
    opt_cfg = AdamWConfig(lr=mix["optimizer"]["lr"])
    opt = on_chip(jax.eval_shape(lambda p: init_opt_state(p, opt_cfg),
                                 params))
    page = 16
    n_pages = S * (-(-M // page)) + S + 1
    pool = jax.eval_shape(lambda: model.init_cache(n_pages, page))
    print(json.dumps({"config": args.config, "traffic": args.traffic,
                      "pool_pages": n_pages,
                      "pool_bytes": nbytes(pool),
                      "params_bytes": nbytes(params),
                      "adamw_bytes": nbytes(opt)}), flush=True)

    rows = (B, M)
    batch = {"tokens": jax.ShapeDtypeStruct(rows, jnp.int32, sharding=chip),
             "loss_mask": jax.ShapeDtypeStruct(rows, jnp.float32,
                                               sharding=chip),
             "advantages": jax.ShapeDtypeStruct(rows, jnp.float32,
                                                sharding=chip),
             "old_logprobs": jax.ShapeDtypeStruct(rows, jnp.float32,
                                                  sharding=chip)}
    step = make_train_step(model, LossConfig(), opt_cfg)
    report("train_step", jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt, batch).compile(), nbytes(pool))

    eng = SlotEngine(model, lambda: None, capacity=S, max_total_len=M,
                     max_gen_len=M, eos_id=2, temperature=1.0, num_pages=2)
    nb = eng._pages_per_seq
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)
    # the pool and its scales donated, as the engine jits it
    report("_paged_decode_fn", jax.jit(
        eng._paged_decode_fn, donate_argnums=(2, 3)).lower(
        params, i32(S), on_chip(pool), {}, i32(S, nb), i32(S),
        on_chip(jax.random.PRNGKey(0))).compile(), nbytes(opt))

    ref = Reference(c)
    p32 = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, jnp.float32, sharding=chip), params)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip)
    compiled = Reference._accumulate.lower(
        ref, p32, p32, i32(1, M), f32(1, M), f32(1, M), f32(1, M), 0.2, 0.28,
        1.0).compile()
    # beside it: float32 weights' m and v (the gradient sum and the
    # weights are its own arguments)
    report("reference_row_grad", compiled, 2 * nbytes(p32))
    return 0


if __name__ == "__main__":
    sys.exit(main())
