"""Parameter-spec machinery.

A model is described once as a pytree of :class:`ParamSpec` (shape, dtype,
logical axis names, initializer).  From that single tree we derive:

* ``init_params``     — materialized weights (PRNG-seeded),
* ``abstract_params`` — ``jax.ShapeDtypeStruct`` stand-ins (dry-run, no alloc),
* ``logical_axes``    — pytree of logical-axis tuples,
* ``shardings``       — pytree of ``NamedSharding`` after applying rules.

Logical→mesh rules implement the Vespa tile plan: the baseline maps model
dimensions to the ``model`` mesh axis; MRA replication (paper C1) remaps a
tile's logical axes onto the ``(replica, shard)`` factoring without touching
the ParamSpec tree — the "accelerator RTL" never changes.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


Axis = Optional[Union[str, Tuple[str, ...]]]


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]          # logical axis name per dim
    dtype: Any = jnp.bfloat16
    init: str = "normal"                     # normal | zeros | ones | small
    scale: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def spec(shape, axes, dtype=jnp.bfloat16, init="normal", scale=0.02) -> ParamSpec:
    return ParamSpec(tuple(shape), tuple(axes), dtype, init, scale)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _tree_map(fn, tree):
    return jax.tree_util.tree_map(fn, tree, is_leaf=is_spec)


def abstract_params(tree):
    return _tree_map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree)


def logical_axes(tree):
    return _tree_map(lambda s: s.axes, tree)


def _init_one(s: ParamSpec, key) -> jax.Array:
    if s.init == "zeros":
        return jnp.zeros(s.shape, s.dtype)
    if s.init == "ones":
        return jnp.ones(s.shape, s.dtype)
    scale = s.scale
    if s.init == "small":
        scale = s.scale / max(1, int(np.sqrt(np.prod(s.shape[:-1]) or 1)))
    x = jax.random.normal(key, s.shape, jnp.float32) * scale
    return x.astype(s.dtype)


def init_params(tree, key):
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    out = [_init_one(s, k) for s, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Logical → mesh rules
# ---------------------------------------------------------------------------

# Baseline rule set for the ("data", "model") production mesh.  Tuples mean
# "sharded over multiple mesh axes".  ``None`` = replicated.
BASE_RULES: Dict[str, Axis] = {
    "layers": None,
    "vocab": "model",
    "embed": None,
    "qkv": "model",          # flattened n_heads*head_dim projection dim
    "kv": "model",           # flattened n_kv_heads*head_dim projection dim
    "heads": "model",
    "ff": "model",
    "ff_in": None,
    "experts": None,         # baseline: expert-TP (shard expert_ff), EP is a variant
    "expert_ff": "model",
    "kv_lora": None,
    "d_inner": "model",      # mamba inner channels
    "ssm_state": None,
    "ssm_heads": "model",
    "conv_ch": "model",
    "norm": None,
}


def rules_with(overrides: Dict[str, Axis]) -> Dict[str, Axis]:
    r = dict(BASE_RULES)
    r.update(overrides)
    return r


def mesh_axis_size(mesh: Mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def partition_spec_for(axes: Tuple[Optional[str], ...],
                       shape: Tuple[int, ...],
                       rules: Dict[str, Axis],
                       mesh: Mesh) -> P:
    """Map logical axes to a PartitionSpec, replicating when not divisible."""
    entries = []
    used: set = set()
    for name, dim in zip(axes, shape):
        ax = rules.get(name) if name is not None else None
        if ax is None:
            entries.append(None)
            continue
        axt = ax if isinstance(ax, tuple) else (ax,)
        if any(a in used for a in axt):
            entries.append(None)        # an axis can shard only one dim
            continue
        if dim % mesh_axis_size(mesh, ax) != 0:
            entries.append(None)        # replicate non-divisible dims
            continue
        used.update(axt)
        entries.append(ax)
    return P(*entries)


def shardings_for(tree, rules: Dict[str, Axis], mesh: Mesh):
    def one(s: ParamSpec):
        return NamedSharding(mesh, partition_spec_for(s.axes, s.shape, rules, mesh))
    return _tree_map(one, tree)


def pspecs_for(tree, rules: Dict[str, Axis], mesh: Mesh):
    def one(s: ParamSpec):
        return partition_spec_for(s.axes, s.shape, rules, mesh)
    return _tree_map(one, tree)


# ---------------------------------------------------------------------------
# Activation sharding helper
# ---------------------------------------------------------------------------


# Batch ("stream") axes are swappable at lowering time: the baseline maps
# batch dims to ("pod", "data"); the FSDP strategy adds "model"; an MRA mesh
# adds "replica" (the AXI bridge splits the stream across tile replicas).
_DEFAULT_BATCH_AXES: Tuple[str, ...] = ("pod", "data")
_BATCH_AXES: Tuple[str, ...] = _DEFAULT_BATCH_AXES


def set_batch_axes(axes: Tuple[str, ...]) -> None:
    global _BATCH_AXES
    _BATCH_AXES = tuple(axes)


def get_batch_axes() -> Tuple[str, ...]:
    return _BATCH_AXES


def shard_activation(x: jax.Array, *axes: Axis) -> jax.Array:
    """``with_sharding_constraint`` that degrades to no-op without a mesh.

    ``axes`` is a per-dim mesh-axis assignment (None = unconstrained).  Safe
    to call from model code unconditionally; under a 1-device test mesh or no
    mesh at all it's the identity.  Any axis equal to the default batch-axes
    tuple is substituted with the currently-configured batch axes.
    """
    axes = tuple(_BATCH_AXES if a == _DEFAULT_BATCH_AXES else a
                 for a in axes)
    try:
        _names = set(jax.sharding.get_abstract_mesh().axis_names)
    except Exception:                                    # pragma: no cover
        _names = set()
    if "model" not in _names and "shard" in _names:
        # MRA-factored mesh: intra-tile model dims live on the "shard"
        # sub-axis; K=1 tiles (MODEL_FULL, e.g. the vocab tile) span both —
        # so "replica" must vacate the batch dims of those tensors
        if "__model_full__" in axes:
            axes = tuple(
                tuple(n for n in a if n != "replica") if isinstance(a, tuple)
                else a for a in axes)
        axes = tuple("shard" if a == "model" else a for a in axes)
        axes = tuple(("replica", "shard") if a == "__model_full__" else a
                     for a in axes)
    else:
        axes = tuple("model" if a == "__model_full__" else a for a in axes)
    try:
        am = jax.sharding.get_abstract_mesh()
    except Exception:                                    # pragma: no cover
        return x
    if am is None or not getattr(am, "axis_names", ()):  # no mesh context
        return x
    names = set(am.axis_names)
    ents = []
    for a in axes[: x.ndim]:
        if a is None:
            ents.append(None)
        elif isinstance(a, tuple):
            present = tuple(n for n in a if n in names)
            ents.append(present if present else None)
        else:
            ents.append(a if a in names else None)
    ents += [None] * (x.ndim - len(ents))
    # drop constraints that don't divide or reuse an axis (first dim wins —
    # matters when the batch axes absorb "model" under the FSDP strategy)
    fixed = []
    used: set = set()
    for dim, a in zip(x.shape, ents):
        if a is None:
            fixed.append(None)
            continue
        names_a = list(a) if isinstance(a, tuple) else [a]
        names_a = [n for n in names_a if n not in used]
        # drop trailing axes until this dim divides (multi-pod FSDP with
        # global_batch < chips falls back to fewer batch axes)
        while names_a:
            size = 1
            for n in names_a:
                size *= am.shape[n]
            if dim % size == 0:
                break
            names_a.pop()
        if names_a:
            ent = tuple(names_a) if len(names_a) > 1 else names_a[0]
            fixed.append(ent)
            used.update(names_a)
        else:
            fixed.append(None)
    return jax.lax.with_sharding_constraint(x, P(*fixed))


def count_params(tree) -> int:
    leaves = jax.tree_util.tree_leaves(tree, is_leaf=is_spec)
    total = 0
    for l in leaves:
        shape = l.shape
        total += int(np.prod(shape)) if len(shape) else 1
    return total
