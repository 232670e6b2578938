"""CPU rehearsals of ``chip_smoke.py`` and the pieces it brings up: the
session phase at tiny widths, the refusal to run without a TPU, replica
placement over four virtual devices, the session's model configuration,
and the compile-cache helper."""
import os
import subprocess
import sys
import textwrap

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.data import logic  # noqa: E402
from repro.rl.session import (RLSession, SessionConfig, sft_warmup,  # noqa: E402
                              tiny_lm_config)

TINY = tiny_lm_config(len(logic.VOCAB), d_model=32, layers=1, heads=2)


def test_session_phase_at_tiny_width():
    """The chip's session phase, unchanged but for the model widths:
    >= 2 finite updates, tokens generated, GRPO prefix sharing and
    resume-from-resident-pages exercised, no leaked pages."""
    facts = chip_smoke.session_phase(TINY)
    assert facts["updates"] >= 2
    assert facts["tokens_generated"] > 0
    assert facts["shared_prefills"] > 0
    assert facts["resumed_without_prefill"] > 0


def test_trace_facts_count_compiles_spans_and_waits():
    """chip_smoke counts compiles with the program's recorder: by the span
    open during each, beside span seconds (whole and self) and the
    trajectories' running and done-to-update times."""
    import time
    import jax.numpy as jnp
    from repro.core import trace
    from repro.core.buffer import Mode, StatefulRolloutBuffer
    rec = trace.enable()
    try:
        with trace.span("trainer.update"):
            with trace.span("trainer.step"):
                jax.jit(lambda x: x * 5.0 - 0.5)(
                    jnp.ones(3)).block_until_ready()
        buf = StatefulRolloutBuffer(Mode.PARTIAL)
        uids = buf.load_prompts([[1, 2], [1, 3], [1, 4]])
        buf.mark_running(uids)
        buf.record_tokens(uids[0], [5], [-1.0], 0)
        buf.scavenge(uids[0])                # harvested, then resumed
        buf.mark_running([uids[0]])
        time.sleep(0.005)
        for u in uids:
            buf.mark_done(u, "length")
        time.sleep(0.005)
        buf.consume(uids[:2])
        facts = chip_smoke.trace_facts(rec, rec.take())
    finally:
        trace.disable()
    assert facts["compiles"] >= 1 and facts["compile_s"] > 0
    assert facts["compiles_by_span"]["trainer.step"] >= 1
    n, whole, own = facts["span_s"]["trainer.update"]
    n_step, step, step_own = facts["span_s"]["trainer.step"]
    assert n == n_step == 1 and step == step_own
    assert whole >= step and own == pytest.approx(whole - step)
    traj = facts["trajectories"]
    assert traj["consumed"] == 2 and traj["harvested"] == 1
    assert traj["run_p90_s"] >= 0.005
    assert traj["done_wait_p90_s"] >= 0.005


def test_kernel_phase_at_tiny_width():
    """The chip's kernel phase in interpret mode: every kernel agrees
    with its oracle, and no compiled program holds a Mosaic call."""
    facts = chip_smoke.kernel_phase(TINY, slots=2, seq=256)
    assert set(facts["max_abs_error"]) == {
        "ragged_decode_attention", "paged_decode_attention",
        "paged_decode_attention_int8", "flash_attention", "fused_sample"}


def test_refuses_a_platform_that_is_not_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


REPLICA_REHEARSAL = textwrap.dedent("""
    import jax
    import chip_smoke
    from repro.data import logic
    from repro.rl.session import RLSession, SessionConfig, tiny_lm_config
    from repro.rl.trainer import TrainState
    tiny = tiny_lm_config(len(logic.VOCAB), d_model=32, layers=1, heads=2)
    print(chip_smoke.replica_phase(tiny, 4))

    # a replica off the default device keeps its own weight copy and
    # re-copies it when the trainer's step advances
    s = RLSession.from_config(SessionConfig(model=tiny, sft_steps=0,
                                            num_replicas=2, rollout_batch=4))
    r1 = s.engine.replicas[1]
    p = r1.params_fn()
    assert r1.params_fn() is p
    new = jax.tree.map(lambda a: a + 1, s.trainer.params())
    s.trainer.state = TrainState(new, s.trainer.state.opt_state,
                                 s.trainer.state.step + 1)
    q = r1.params_fn()
    assert q is not p
    dev = jax.local_devices()[1]
    for a, b in zip(jax.tree.leaves(q), jax.tree.leaves(new)):
        assert a.devices() == {dev}
        assert (jax.device_get(a) == jax.device_get(b)).all()
    print("refresh ok")
""")


def test_replica_phase_on_four_virtual_devices():
    """The four-chip phase on four virtual CPU devices: replica i on
    device i, token streams identical to one engine; plus the weight
    refresh of an off-default-device replica."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", REPLICA_REHEARSAL],
                          capture_output=True, text=True, env=env,
                          timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "'replicas': 4" in proc.stdout and "refresh ok" in proc.stdout


def test_replicas_share_the_default_device_on_one_chip():
    """With one device every replica stays where it was: default device,
    the trainer's own params callback, no copies."""
    s = RLSession.from_config(SessionConfig(model=TINY, sft_steps=0,
                                            num_replicas=2, rollout_batch=4))
    dev = jax.devices()[0]
    for r in s.engine.replicas:
        assert r.params_fn == s.trainer.params
        assert all(a.devices() == {dev} for a in r.cache.values())


def test_session_builds_the_configured_model():
    s = RLSession.from_config(SessionConfig(model=TINY, sft_steps=0))
    assert s.model.cfg is TINY
    assert s.engine.model.cfg.d_model == 32


def test_session_rejects_a_model_vocab_narrower_than_the_task():
    narrow = TINY.replace(vocab_size=len(logic.VOCAB) - 1)
    with pytest.raises(ValueError, match="cannot embed"):
        RLSession.from_config(SessionConfig(model=narrow, sft_steps=0))


def test_task_vocab_decodes_wider_model_ids_as_unknown():
    from repro.data.tokenizer import UNK
    v = logic.VOCAB
    assert v.decode([v.eos_id, len(v), 151935]) == ["<eos>", UNK, UNK]
    meta = logic.LogicTaskGenerator(seed=0).batch(1)[1][0]
    assert logic.verify([len(v)] * 8, meta) == 0.0


def test_sft_warmup_without_steps_returns_params_untouched():
    params = {"w": jax.numpy.ones(3)}
    out, losses = sft_warmup(None, params, [], pad_id=0, steps=0)
    assert out is params and losses == []


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    from repro.compile_cache import REPO_ROOT, enable_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was   # nothing set
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        assert path == os.path.join(REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


def test_chip_smoke_alone_fails(tmp_path):
    """Without the repo around it the script cannot import the system,
    so it fails and prints no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
