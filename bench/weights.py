"""Weights drawn from the run's seed, in the layout the program reads.

The tree's structure and shapes come from ``jax.eval_shape`` of the
program's initialiser (nothing of it runs); the values are the
benchmark's own, made on the device in one jitted call, in the dtype they
are served in.  The plain reference reads the same tree, so the program
and the reference start from identical weights that neither made.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench import family


def seed_words(seed: int) -> Tuple[int, int]:
    """Low and high 32-bit words: PRNGKey alone drops bits above 32."""
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


@functools.lru_cache(maxsize=4)
def weight_fn(model, init_std, init_range: float):
    """Jitted (lo, hi) -> params for this model, each leaf's (mean, std)
    from its family's ``init_std``."""
    template = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)

    @jax.jit
    def build(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        out = []
        for i, (path, sd) in enumerate(leaves):
            mean, std = init_std(leaf_name(path), init_range)
            z = jax.random.normal(jax.random.fold_in(key, i), sd.shape,
                                  jnp.float32)
            out.append((mean + std * z).astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


def make_weights(model, c: Dict, seed: int) -> Dict:
    """The weights of ``model``, built from configuration ``c``."""
    lo, hi = seed_words(seed)
    build = weight_fn(model, family.load(c).init_std, c["initializer_range"])
    return build(jnp.uint32(lo), jnp.uint32(hi))


def leaf_names(tree) -> List[str]:
    """Names of the entries of :func:`leaf_norms`' vector: stacked layer
    leaves count once per layer."""
    names = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        n = leaf_name(path)
        if n.startswith("layers/"):
            names += [f"{n}[{i}]" for i in range(x.shape[0])]
        else:
            names.append(n)
    return names


@jax.jit
def leaf_norms(tree) -> jnp.ndarray:
    """f32 L2 norm of every leaf, and of every layer's slice of a stacked
    leaf, as one vector (order of :func:`leaf_names`)."""
    out = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(jnp.float32)
        if leaf_name(path).startswith("layers/"):
            out.append(jnp.sqrt(jnp.sum(jnp.square(x).reshape(x.shape[0], -1),
                                        axis=1)))
        else:
            out.append(jnp.sqrt(jnp.sum(jnp.square(x)))[None])
    return jnp.concatenate(out)


@jax.jit
def diff_norms(a, b) -> jnp.ndarray:
    """:func:`leaf_norms` of a - b, computed in f32."""
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))
