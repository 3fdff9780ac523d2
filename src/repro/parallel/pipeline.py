"""Pipeline parallelism: GPipe-schedule microbatch pipeline over a
``stage`` mesh axis, built from shard_map + lax.ppermute.

Vespa mapping: pipeline stages are frequency islands in series — each
stage is a tile group on its own sub-mesh, and the stage boundary is a
resynchronizer (one ppermute per clock tick).  The DFS straggler policy
derates early stages to the slowest stage's rate instead of letting
bubbles idle-burn (core/dfs.policy_straggler).

Schedule: fill-drain (GPipe).  With M microbatches and S stages the bubble
fraction is (S-1)/(M+S-1); the backward pass is derived by autodiff
(ppermute transposes to the reverse permute), which makes this a correct —
if not 1F1B-scheduled — pipeline.  1F1B is a scheduling refinement on the
same substrate, recorded as future work.

Usage (inside or outside jit):

    y = pipeline_apply(stage_fn, stage_params, x, mesh=mesh,
                       axis="stage", n_micro=8)

* ``stage_params``: pytree whose leaves have a leading ``n_stages`` dim
  (stage s uses leaf[s]).
* ``stage_fn(params_slice, x_mb) -> y_mb`` must keep the microbatch shape
  (homogeneous stages — reshape layers into equal groups).
* ``x``: (batch, ...) — split into ``n_micro`` microbatches on axis 0.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp


P = jax.sharding.PartitionSpec


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jax.Array,
                   *, mesh, axis: str = "stage", n_micro: int = 4
                   ) -> jax.Array:
    """Run ``x`` through ``n_stages`` sequential stages, pipelined."""
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro
    xm = x.reshape((n_micro, mb) + x.shape[1:])

    def body(params_local, xm_local):
        # params_local: stage slice (leading dim 1) ; xm_local: full (M, mb, ...)
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
        s = jax.lax.axis_index(axis)
        S = n_stages            # static (jax.lax.axis_size is newer jax)
        M = xm_local.shape[0]
        T = M + S - 1
        fwd = [(i, (i + 1) % S) for i in range(S)]   # ring step (wraps; the
        #        wrapped value is masked out by the validity window below)

        def step(carry, t):
            buf, outs = carry                          # buf: (mb, ...)
            mb_idx = jnp.clip(t - s, 0, M - 1)
            valid = (t >= s) & (t - s < M)
            inp = jnp.where(s == 0,
                            xm_local[mb_idx].astype(buf.dtype), buf)
            out = stage_fn(params_local, inp)
            out = jnp.where(valid, out, 0.0)
            # last stage banks its result; others forward it
            outs = jnp.where(
                valid & (s == S - 1),
                jax.lax.dynamic_update_index_in_dim(
                    outs, out.astype(outs.dtype), mb_idx, 0),
                outs)
            buf_next = jax.lax.ppermute(out, axis, fwd)
            return (buf_next, outs), None

        buf0 = jnp.zeros(xm_local.shape[1:], jnp.float32)
        outs0 = jnp.zeros_like(xm_local, dtype=jnp.float32)
        (_, outs), _ = jax.lax.scan(step, (buf0, outs0), jnp.arange(T))
        # every device returns outs; only the last stage's is real — psum
        # after masking (cheap: it is exact for S-1 zero contributions)
        outs = jnp.where(s == S - 1, outs, 0.0)
        return jax.lax.psum(outs, axis)

    params_specs = jax.tree_util.tree_map(
        lambda a: P(axis), stage_params)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(params_specs, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, xm)
    return out.reshape((B,) + out.shape[2:]).astype(x.dtype)


def stack_layer_groups(stacked_params: Any, n_stages: int) -> Any:
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-stacked."""
    def one(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])
    return jax.tree_util.tree_map(one, stacked_params)
