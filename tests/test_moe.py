"""MoE: capacity-dispatch path vs the loop-over-experts oracle, aux-loss
sanity, capacity-drop behaviour."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_smoke_config
from repro.models import moe as MOE

KEY = jax.random.PRNGKey(5)


def _setup(capacity_factor=8.0, experts=4, k=2):
    cfg = get_smoke_config("granite_moe_3b_a800m")
    cfg = cfg.replace(param_dtype=jnp.float32, compute_dtype=jnp.float32,
                      moe=cfg.moe.__class__(
                          num_experts=experts, experts_per_token=k,
                          d_ff_expert=32, capacity_factor=capacity_factor))
    p = MOE.init_moe_mlp(KEY, cfg, jnp.float32)
    return cfg, p


def test_dense_dispatch_matches_oracle():
    """With generous capacity (no drops) the scatter/gather path equals the
    explicit loop over experts."""
    cfg, p = _setup(capacity_factor=8.0)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 16, cfg.d_model))
    y, aux = MOE.moe_mlp_dense(p, cfg, x)
    y_ref = MOE.moe_mlp_ref(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
    assert float(aux["load_balance"]) > 0


def test_capacity_drop():
    """With capacity_factor << 1 some tokens are dropped (output zero for
    their expert contribution) but nothing NaNs."""
    cfg, p = _setup(capacity_factor=0.1)
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 32, cfg.d_model))
    y, _ = MOE.moe_mlp_dense(p, cfg, x)
    y_ref = MOE.moe_mlp_ref(p, cfg, x)
    assert not bool(jnp.isnan(y).any())
    # dropped tokens make outputs differ from the no-drop oracle
    assert float(jnp.abs(y - y_ref).max()) > 1e-3


def test_dispatch_indices_capacity_order():
    idx = jnp.array([[0], [0], [0], [1]])
    pos, keep = MOE._dispatch_indices(idx, E=2, C=2)
    np.testing.assert_array_equal(np.asarray(pos[:, 0]), [0, 1, 2, 0])
    np.testing.assert_array_equal(np.asarray(keep[:, 0]),
                                  [True, True, False, True])


def test_ep_path_matches_dense_single_device():
    """shard_map EP path on a 1x1 mesh == dense-dispatch path."""
    from repro.launch.mesh import make_auto_mesh
    cfg, p = _setup(capacity_factor=8.0, experts=4, k=2)
    mesh = make_auto_mesh((1, 1), ("data", "model"))
    x = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 16, cfg.d_model))
    y_ep, aux_ep = MOE.moe_mlp_ep(p, cfg, x, mesh)
    y_d, _ = MOE.moe_mlp_dense(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_d), atol=2e-5)
