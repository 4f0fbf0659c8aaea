"""Production mesh + sharding rule tables (DESIGN.md §4).

``make_production_mesh`` is a FUNCTION so importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before first jax init).

Rule tables map *logical* axes (nn/module.py ParamSpec) → mesh axes:

* train/prefill — TP on ``model`` (heads/mlp/experts/vocab), FSDP on ``data``
  (+``pod`` when present) for the embed dimension; batch on data(+pod).
* decode — same parameter layout (weights stay sharded; GSPMD inserts the
  per-layer gathers we analyze in §Roofline); KV caches shard batch on
  data(+pod) and sequence on ``model``(flash-decode style).

1-D params (norm gains, biases) are always replicated — sub-kilobyte, and
uneven shardings of tiny vectors buy nothing.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.nn.module import ParamSpec, logical_to_pspec

__all__ = [
    "make_production_mesh",
    "sharding_rules",
    "param_pspecs",
    "param_shardings",
    "batch_axes",
]


def _mk(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devs)} — the dry-run "
            "must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import (launch/dryrun.py does)."
        )
    auto = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=auto, devices=devs[:n])


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh (tests, elastic re-shard targets)."""
    return _mk(shape, axes)


def batch_axes(mesh: Mesh):
    """Mesh axes the global batch shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def sharding_rules(mesh: Mesh, kind: str = "train", **overrides) -> Dict[str, object]:
    """Logical-axis → mesh-axis table for ``kind`` ∈ {train, prefill, decode}.

    ``act_seq`` governs the *layer-boundary activation carry* (models/lm.py):
    sharding it on ``model`` is Megatron-style sequence parallelism — the
    remat-saved [B, S, d] per layer drops 16×, at the price of per-layer
    gather/scatter collectives.  Default on for train/prefill (required to
    fit the 405B/671B train cells in 16 GB); the §Perf baseline measures the
    unsharded variant via ``overrides``.
    """
    multi = "pod" in mesh.axis_names
    fsdp = ("pod", "data") if multi else ("data",)
    rules: Dict[str, object] = {
        "batch": fsdp,
        "embed": fsdp,            # FSDP: weight rows sharded over data(+pod)
        "embed2": None,
        "heads_flat": "model",    # TP: flattened H·D (divisible by 16 everywhere)
        "mlp": "model",
        "experts": "model",       # EP: routed experts over model
        "vocab": "model",
        "layers": None,           # scanned axis — never sharded
        "seq": None,
        "act_seq": "model" if kind in ("train", "prefill") else None,
        "kv_seq": "model",        # decode caches: sequence-sharded (flash-decode)
        "capacity": fsdp,         # MoE dispatch buffer token axis
    }
    rules.update(overrides)
    return rules


def param_pspecs(spec_tree, rules: Dict[str, object], mesh: Optional[Mesh] = None):
    """ParamSpec tree → PartitionSpec tree; 1-D params replicated.

    With ``mesh`` given, any dim not divisible by its assigned mesh axes is
    left unsharded (e.g. hymba's vocab 32001 — prime-ish table sizes exist
    in the wild and must not crash the launcher).
    """

    def one(s: ParamSpec):
        if len(s.shape) <= 1:
            return P()
        spec = logical_to_pspec(s.logical_axes, rules)
        if mesh is None:
            return spec
        entries = list(spec) + [None] * (len(s.shape) - len(spec))
        out = []
        for dim, ax in zip(s.shape, entries):
            axes = (ax,) if isinstance(ax, str) else (ax or ())
            size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
            out.append(ax if size and dim % size == 0 else None)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    return jax.tree.map(one, spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def param_shardings(spec_tree, mesh: Mesh, rules: Dict[str, object]):
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p),
        param_pspecs(spec_tree, rules),
        is_leaf=lambda x: isinstance(x, P),
    )
