"""Random weights from the run's seed, made on the device in one jitted call.

The benchmark, not the program, makes the weights, so that the reference
takes nothing the program made.  The tree's layout and leaf types are the
program's interface (``lm.param_spec``); the values are drawn here:

* norm gains: ones;
* the embedding table: normal, standard deviation 0.02;
* every other matrix (projections, the untied LM head): normal with
  standard deviation ``1 / sqrt(fan_in)``, ``fan_in`` being the input width.

A model family may export ``draw(name, shape, dtype, key)`` for leaves this
rule does not cover (a 1-D routing bias, say): it is asked first for every
leaf, and where it returns None the rule above draws the leaf.

Stacked per-layer leaves ``[L, ...]`` are drawn one layer at a time inside
the call, so the float32 transient is one layer's leaf, not the stack's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds may pass 32 bits)."""
    words = np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF,
         int(int(seed) < 0)]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def _stacked(path) -> bool:
    return any(getattr(p, "key", None) == "segments" for p in path)


def _draw(name: str, shape, dtype, key):
    if "norm" in name or name.startswith("ln"):
        return jnp.ones(shape, dtype)
    std = EMBED_STD if name == "embed" else 1.0 / np.sqrt(shape[-2])
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_params(abstract_tree, seed: int, draw=None):
    """Draw every leaf of ``abstract_tree`` (ShapeDtypeStructs) from ``seed``,
    asking the family's ``draw`` first where one is given."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)

    def _pick(name, shape, dtype, key):
        out = None if draw is None else draw(name, shape, dtype, key)
        return _draw(name, shape, dtype, key) if out is None else out

    def gen(key):
        out = []
        for i, (path, sds) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            name = _leaf_name(path)
            if _stacked(path) and len(sds.shape) >= 3:
                per = sds.shape[1:]
                out.append(jax.lax.map(
                    lambda l, k=k, name=name, per=per, dt=sds.dtype:
                    _pick(name, per, dt, jax.random.fold_in(k, l)),
                    jnp.arange(sds.shape[0])))
            else:
                out.append(_pick(name, sds.shape, sds.dtype, k))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(gen)(key_from_seed(seed))
