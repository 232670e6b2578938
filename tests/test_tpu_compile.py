"""Compiles for a described TPU v5e (``v5e:2x2``, one chip used) at
Qwen3-0.6B widths: the five Pallas kernels, and the engine's paged decode
step and the trainer's update step at ``chip_smoke.py``'s session size.

Nothing runs: a compile that the chip's compiler would refuse (a block
not aligned to the (8, 128) tiling, a primitive with no Mosaic lowering,
a program that does not fit the chip's HBM) fails here.  The topology is
described inside a fixture, never at import, and JAX's persistent compile
cache is off around these compiles (an entry for a described chip cannot
be read back without one).
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.ragged_decode_attention import (  # noqa: E402
    fused_sample, paged_decode_attention, ragged_decode_attention)

V5E_HBM_BYTES = 15.75e9          # what the v5e compiler reports usable

QWEN = chip_smoke.qwen3_0_6b()
H, KH, D = QWEN.num_heads, QWEN.num_kv_heads, QWEN.resolved_head_dim
SMOKE = chip_smoke.session_config(QWEN)
SLOTS, MAX_LEN = SMOKE.rollout_batch, SMOKE.max_total_len
CELL_SLOTS, CELL_LEN = 16, 1024      # the grpo-longtail benchmark cell


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # no TPU compiler on this host
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def sds(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def on_chip(chip, tree):
    return jax.tree.map(lambda a: sds(chip, a.shape, a.dtype), tree)


def compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_ragged_decode_attention_compiles(chip):
    bf16 = jnp.bfloat16
    compile_kernel(
        lambda q, k, v, n: ragged_decode_attention(q, k, v, n,
                                                   interpret=False),
        sds(chip, (SLOTS, H, D), bf16), sds(chip, (SLOTS, MAX_LEN, KH, D), bf16),
        sds(chip, (SLOTS, MAX_LEN, KH, D), bf16), sds(chip, (SLOTS,), jnp.int32))


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("page", [16, 128])
def test_paged_decode_attention_compiles(chip, page, int8):
    """The decode attention on the grpo-longtail cell's layered pool:
    28 layers, 16 slots x 1024 tokens, a table of 1024 / page pages."""
    nb = CELL_LEN // page
    n_pages = CELL_SLOTS * nb + CELL_SLOTS + 1
    dtype = jnp.int8 if int8 else jnp.bfloat16
    pool = (QWEN.num_layers, n_pages, page, KH, D)
    args = [sds(chip, (CELL_SLOTS, H, D), jnp.bfloat16),
            sds(chip, pool, dtype), sds(chip, pool, dtype),
            sds(chip, (CELL_SLOTS, nb), jnp.int32),
            sds(chip, (CELL_SLOTS,), jnp.int32), sds(chip, (), jnp.int32),
            sds(chip, (CELL_SLOTS, KH, D), jnp.bfloat16),
            sds(chip, (CELL_SLOTS, KH, D), jnp.bfloat16)]
    if int8:
        args += [sds(chip, pool[:2], jnp.float32)] * 2

    def fn(q, kp, vp, bt, n, layer, kn, vn, ks=None, vs=None):
        return paged_decode_attention(q, kp, vp, bt, n, layer, kn, vn,
                                      k_scales=ks, v_scales=vs,
                                      interpret=False)
    compile_kernel(fn, *args)


def test_paged_attention_serves_a_narrow_head_dim(chip, monkeypatch):
    """head_dim 64 (Qwen2.5-0.5B widths: 24 layers, 14 / 2 heads, d 896,
    ffn 4864), the grpo-longtail cell's 16 slots x 1024: the page copies
    of the kernel do not compile at 64 lanes, so the engine's own donated
    decode program serves the jnp reference there.  It still works on the
    pool in place: it aliases the pool and needs a small part of one pool
    in temporaries (no copy of the pool, which the device lays out with
    the page axis minor at this width)."""
    from repro import kernels
    from repro.configs.base import AttnConfig
    from repro.models.model import build_model
    from repro.rollout.engine import SlotEngine
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    cfg = QWEN.replace(name="qwen2.5-0.5b-widths", num_layers=24,
                       d_model=896, num_heads=14, num_kv_heads=2,
                       head_dim=64, d_ff=4864,
                       attn=AttnConfig(rope_theta=1_000_000.0))
    model = build_model(cfg)
    params = on_chip(chip, jax.eval_shape(model.init_params,
                                          jax.random.PRNGKey(0)))
    eng = SlotEngine(model, lambda: None, capacity=CELL_SLOTS,
                     max_total_len=CELL_LEN, max_gen_len=CELL_LEN, eos_id=2,
                     temperature=SMOKE.temperature, num_pages=2)
    nb = eng._pages_per_seq
    pool = pool_shapes(model, CELL_SLOTS, CELL_LEN)
    compiled = eng._decode_program(nb).lower(
        params, sds(chip, (CELL_SLOTS,), jnp.int32), on_chip(chip, pool),
        {}, sds(chip, (CELL_SLOTS, nb), jnp.int32),
        sds(chip, (CELL_SLOTS,), jnp.int32),
        on_chip(chip, jax.random.PRNGKey(0))).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= nbytes(pool)
    assert m.temp_size_in_bytes < nbytes(pool) / 20, m.temp_size_in_bytes


@pytest.mark.parametrize("packed", [False, True], ids=["causal", "packed"])
def test_flash_attention_compiles(chip, packed):
    B, bf16 = 2, jnp.bfloat16
    args = [sds(chip, (B, MAX_LEN, H, D), bf16),
            sds(chip, (B, MAX_LEN, KH, D), bf16),
            sds(chip, (B, MAX_LEN, KH, D), bf16)]
    if packed:
        args.append(sds(chip, (B, MAX_LEN), jnp.int32))

    def fn(q, k, v, seg=None):
        return flash_attention(q, k, v, seg_ids=seg, interpret=False)
    compile_kernel(fn, *args)


def test_fused_sample_compiles(chip):
    compile_kernel(
        lambda x, w: fused_sample(x, w, interpret=False),
        sds(chip, (SLOTS, QWEN.d_model), jnp.bfloat16),
        sds(chip, (QWEN.d_model, QWEN.vocab_size), jnp.bfloat16))


@pytest.fixture(scope="module")
def qwen_params(chip):
    from repro.models.model import build_model
    model = build_model(QWEN)
    return model, on_chip(chip, jax.eval_shape(model.init_params,
                                               jax.random.PRNGKey(0)))


def fits(compiled, resident: int = 0) -> float:
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes + resident)
    assert need < V5E_HBM_BYTES, f"{need / 1e9:.2f} GB > v5e HBM"
    return need


def pool_shapes(model, slots=SLOTS, max_len=MAX_LEN):
    """An engine's page pool: one table width of pages per slot, plus
    copy-on-write headroom and the garbage page."""
    page = 16
    n_pages = slots * (max_len // page) + slots + 1
    return jax.eval_shape(lambda: model.init_cache(n_pages, page))


def nbytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


@pytest.mark.parametrize("slots,max_len", [(SLOTS, MAX_LEN),
                                           (CELL_SLOTS, CELL_LEN)],
                         ids=["smoke", "cell"])
def test_engine_paged_decode_step_compiles_and_fits(chip, qwen_params,
                                                    monkeypatch, slots,
                                                    max_len):
    """The engine's own decode program at full block-table width, for the
    session of chip_smoke.py and for the grpo-longtail cell, compiles
    with the Pallas attention (the default backend here is the CPU, so
    the test steers the choice), fits beside the trainer's resident state
    (params + AdamW moments), aliases the donated pool and needs under
    0.25 GB of temporaries: no gathered view, no pool copy."""
    from repro import kernels
    from repro.rollout.engine import SlotEngine
    monkeypatch.setattr(kernels, "default_interpret", lambda: False)
    model, params = qwen_params
    # a two-page engine: only the shapes handed to the step matter
    eng = SlotEngine(model, lambda: None, capacity=slots,
                     max_total_len=max_len, max_gen_len=max_len,
                     eos_id=2, temperature=SMOKE.temperature, num_pages=2)
    nb = eng._pages_per_seq
    pool = pool_shapes(model, slots, max_len)
    compiled = eng._decode_program(nb).lower(
        params, sds(chip, (slots,), jnp.int32), on_chip(chip, pool), {},
        sds(chip, (slots, nb), jnp.int32), sds(chip, (slots,), jnp.int32),
        on_chip(chip, jax.random.PRNGKey(0))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= nbytes(pool)
    assert m.temp_size_in_bytes < 0.25e9, m.temp_size_in_bytes
    moments = 2 * 4 * QWEN.param_count()          # f32 AdamW m and v
    fits(compiled, resident=moments)


def test_train_step_compiles_and_fits(chip, qwen_params):
    """The trainer's donated update step at the session's update size
    (update_batch rows x max_total_len, remat) fits beside the engine's
    page pool."""
    from repro.rl.losses import LossConfig
    from repro.rl.trainer import make_train_step
    from repro.train.optimizer import AdamWConfig, init_opt_state
    model, params = qwen_params
    opt = on_chip(chip, jax.eval_shape(
        lambda p: init_opt_state(p, AdamWConfig()), params))
    rows = (SMOKE.update_batch, MAX_LEN)
    batch = {"tokens": sds(chip, rows, jnp.int32),
             "loss_mask": sds(chip, rows, jnp.float32),
             "advantages": sds(chip, rows, jnp.float32),
             "old_logprobs": sds(chip, rows, jnp.float32)}
    step = make_train_step(model, LossConfig(), AdamWConfig())
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt, batch).compile()
    fits(compiled, resident=nbytes(pool_shapes(model)))
