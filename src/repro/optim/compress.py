"""Gradient compression for the interconnect island (distributed-opt trick).

Cross-pod gradient reduction is the longest-haul traffic in the production
mesh (the ``pod`` axis models the inter-pod/DCN hop).  When the NoC island's
DFS rate is lowered — or when the fabric is the measured bottleneck — the
runtime can switch the pod-axis reduction to int8:

    q = round(g / scale) : int8, scale = max|g| / 127 per leaf
    all_gather(q, 'pod') -> dequant + sum in f32

Wire bytes drop 4x vs f32 (2x vs bf16) at a quantization error that a
per-leaf scale keeps below ~1% of the gradient norm (tests/test_optim.py
asserts this).  This is precision-island switching — a Vespa DFS actuator
lever, not just an optimizer flag (DESIGN.md §C2 actuator list).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp


P = jax.sharding.PartitionSpec


def quantize_int8(g: jax.Array) -> Tuple[jax.Array, jax.Array]:
    amax = jnp.max(jnp.abs(g.astype(jnp.float32)))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum_leaf(g: jax.Array, axis: str) -> jax.Array:
    """int8 all-gather + f32 sum over one mesh axis; call under shard_map."""
    q, scale = quantize_int8(g)
    qs = jax.lax.all_gather(q, axis)               # (n, ...)
    ss = jax.lax.all_gather(scale, axis)           # (n,)
    deq = qs.astype(jnp.float32) * ss.reshape((-1,) + (1,) * g.ndim)
    return jnp.sum(deq, axis=0).astype(g.dtype)


def compressed_allreduce(grads: Any, mesh, axis: str = "pod") -> Any:
    """Compress-reduce a *pod-sharded partial* gradient pytree over ``axis``.

    Expects grads whose values are per-pod partial sums (e.g. produced under
    shard_map with no psum over the pod axis); returns fully-summed grads.
    """
    def body(g):
        return jax.tree_util.tree_map(
            lambda l: compressed_psum_leaf(l, axis), g)

    # every leaf fully replicated within the pod slice; sharded over axis
    spec = P()   # logical view: identical shapes per pod; axis is vmapped
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec,), out_specs=spec,
                         check_vma=False)(grads)
