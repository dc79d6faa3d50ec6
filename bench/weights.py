"""Random weights for a cell, drawn on the device from the run's seed.

The tree's structure and shapes are those the program's wrapper expects
(``jax.eval_shape`` of its initializer); the values are the benchmark's
own, drawn in one jitted call in the serving dtype, so the plain reference
can take the same arrays without taking anything the program made.

Each leaf is drawn by its name:

* norm weights: ``1 + 0.1 * normal`` (so a reference that drops a norm
  weight disagrees);
* Mamba2 ``a_log``: ``log(uniform(1, 16))``; ``dt_bias``: the inverse
  softplus of ``dt ~ log-uniform(1e-3, 1e-1)``; ``d_skip``: ones (the
  Mamba2 initialization);
* every matrix: ``normal / sqrt(fan_in)``, with fan-in the contracted
  width (``d_model`` for the q/k/v projections): the product of the axes
  after the leaf's stack axes (layers, experts; the family's ``STACKED``,
  ``bench/backbones/<family>.py``) but the last;
* the unused token embedding: zeros.
"""
from __future__ import annotations

import math

import backbones

NORMS = {"ln", "ln1", "ln2", "ln_in", "final_norm", "out_norm", "gate_norm"}


def _fan_in(name: str, shape: tuple) -> int:
    if name in ("wq", "wk", "wv"):
        return shape[0]
    return max(1, math.prod(shape[:-1]))


def _leaf(name: str, stack_axes: int, shape, dtype, key):
    import jax
    import jax.numpy as jnp

    per = shape[stack_axes:]
    if name in NORMS:
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "a_log":
        w = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        w = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "d_skip":
        w = jnp.ones(shape, jnp.float32)
    elif name == "tok":
        w = jnp.zeros(shape, jnp.float32)
    else:
        w = (jax.random.normal(key, shape, jnp.float32)
             / math.sqrt(_fan_in(name, per)))
    return w.astype(dtype)


def draw(structure, seed: int, family: str):
    """Weights shaped like ``structure`` (a pytree of ShapeDtypeStructs) of
    a ``family`` backbone, drawn from ``seed`` in one jitted program."""
    import jax

    stacked = backbones.load(family).STACKED
    paths, treedef = jax.tree_util.tree_flatten_with_path(structure)

    def keyname(k):
        return getattr(k, "key", getattr(k, "name", str(k)))

    specs = []
    for path, leaf in paths:
        names = [keyname(k) for k in path]
        specs.append((names[-1], sum(stacked.get(n, 0) for n in names),
                      tuple(leaf.shape), leaf.dtype))

    def make(key):
        keys = jax.random.split(key, len(specs))
        return [_leaf(n, st, sh, dt, k) for (n, st, sh, dt), k
                in zip(specs, keys)]

    key = jax.random.PRNGKey(seed % (2 ** 31))
    return jax.tree_util.tree_unflatten(treedef, jax.jit(make)(key))
