"""Functional parameter-tree module system with logical-axis sharding.

MaxText-style: modules build trees of :class:`ParamSpec` descriptors carrying
*logical* axis names; the tree can be

* ``abstract()``-ed into ``jax.ShapeDtypeStruct``s (dry-run lowering — no
  allocation ever happens for the full-size configs),
* ``materialize()``-d into real arrays (tests, examples, training),
* mapped to ``PartitionSpec``s via a per-config rule table (``pspec_tree``).

Sharding rules map logical axis → mesh axis (or None).  A mesh axis may not
appear twice in one param's spec; later (lower-priority) occurrences are
dropped — this keeps rule tables small and lets one table serve every layer.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["ParamSpec", "abstract", "materialize", "pspec_tree", "shardings", "count_params"]


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical_axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"          # normal | zeros | ones | fan_in
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.logical_axes), (self.shape, self.logical_axes)


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def abstract(tree):
    """ParamSpec tree → ShapeDtypeStruct tree (no device memory touched)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree, is_leaf=_is_spec
    )


def _init_one(spec: ParamSpec, key) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "fan_in":
        fan_in = spec.shape[0] if len(spec.shape) <= 2 else int(np.prod(spec.shape[:-1]))
        std = spec.scale / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(spec.dtype)
    return (jax.random.normal(key, spec.shape, jnp.float32) * (0.02 * spec.scale)).astype(spec.dtype)


def materialize(tree, key):
    """ParamSpec tree → initialized array tree (deterministic per-leaf keys)."""
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_spec)
    keys = jax.random.split(key, len(leaves))
    # one leaf at a time: queued eager draws would hold several float32
    # transients at once (phi4-mini's stacked MLP weight alone is 3.2 GB)
    return jax.tree.unflatten(treedef, [_init_one(s, k).block_until_ready()
                                        for s, k in zip(leaves, keys)])


def logical_to_pspec(axes: Sequence[Optional[str]], rules: Dict[str, Optional[str]]) -> P:
    """Map logical axes → PartitionSpec under ``rules``, dropping repeats.

    A rule value may be a single mesh axis, a tuple of mesh axes (e.g.
    ``("data", "model")`` for fully-sharded giant tables), or None.
    """
    used: set = set()
    out = []
    for ax in axes:
        m = rules.get(ax) if ax is not None else None
        if m is None:
            out.append(None)
            continue
        ms = (m,) if isinstance(m, str) else tuple(m)
        ms = tuple(x for x in ms if x not in used)
        if not ms:
            out.append(None)
        elif len(ms) == 1:
            out.append(ms[0])
            used.add(ms[0])
        else:
            out.append(ms)
            used.update(ms)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def pspec_tree(tree, rules: Dict[str, Optional[str]]):
    return jax.tree.map(
        lambda s: logical_to_pspec(s.logical_axes, rules), tree, is_leaf=_is_spec
    )


def shardings(tree, mesh, rules: Dict[str, Optional[str]]):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, logical_to_pspec(s.logical_axes, rules)),
        tree,
        is_leaf=_is_spec,
    )


def count_params(tree) -> int:
    leaves = jax.tree.leaves(tree, is_leaf=_is_spec)
    return sum(int(np.prod(l.shape)) for l in leaves)
