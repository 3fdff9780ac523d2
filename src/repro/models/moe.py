"""Mixture-of-Experts FFN: dropless sort + ``jax.lax.ragged_dot`` dispatch.

TPU adaptation notes (DESIGN.md §2): GPU MoE kernels scatter tokens with
atomics; the TPU-idiomatic form is sort-by-expert + grouped matmul
(``ragged_dot``), which keeps the MXU busy on contiguous tiles.

Sharding: tokens are data-parallel, experts are **expert-TP** in the
baseline — every expert's FFN is sharded over the ``model`` axis on the
d_ff dim, so MoE comms equal dense-MLP comms (one psum).  Routing/sort stays
*local* to each data shard by construction (shard_map), avoiding a global
sort.  Expert-parallel all-to-all dispatch is the Vespa-MRA variant
(core/replication.py) explored in §Perf.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models.params import spec, get_batch_axes
from repro.models.layers import _act, DATA, MODEL


P = jax.sharding.PartitionSpec


def moe_spec(cfg: ArchConfig):
    d, E, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    out = {
        "router": spec((d, E), ("embed", "experts"), dtype=jnp.float32),
        "wi_gate": spec((E, d, f), ("experts", "embed", "expert_ff")),
        "wi_up": spec((E, d, f), ("experts", "embed", "expert_ff")),
        "wo": spec((E, f, d), ("experts", "expert_ff", "embed"), init="small"),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        out["shared"] = {
            "wi_gate": spec((d, fs), ("embed", "ff")),
            "wi_up": spec((d, fs), ("embed", "ff")),
            "wo": spec((fs, d), ("ff", "embed"), init="small"),
        }
    return out


def _route(router_w: jax.Array, x: jax.Array, top_k: int):
    """Token->expert assignment.  x: (N,d).  Returns gates (N,k) f32, ids (N,k)."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)   # (N,E)
    top_logits, top_ids = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(top_logits, axis=-1)
    return gates, top_ids, logits


def _moe_ffn_local(p: Dict, x: jax.Array, cfg: ArchConfig) -> Tuple[jax.Array, jax.Array]:
    """Per-shard dropless MoE.  x: (N,d) local tokens; expert weights are the
    local d_ff shard.  Returns (out (N,d) [partial over model axis], aux loss).
    """
    N, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    gates, top_ids, logits = _route(p["router"], x, k)

    # flatten (token, slot) pairs and sort by expert
    flat_ids = top_ids.reshape(-1)                        # (N*k,)
    sort_idx = jnp.argsort(flat_ids)                      # stable
    tok_idx = sort_idx // k                               # token of each row
    xs = jnp.take(x, tok_idx, axis=0)                     # (N*k, d)
    group_sizes = jnp.bincount(flat_ids, length=E).astype(jnp.int32)

    h = _act(jax.lax.ragged_dot(xs, p["wi_gate"], group_sizes), cfg.act)
    h = h * jax.lax.ragged_dot(xs, p["wi_up"], group_sizes)
    ys = jax.lax.ragged_dot(h, p["wo"], group_sizes)      # (N*k, d)

    gate_sorted = jnp.take(gates.reshape(-1), sort_idx, axis=0)
    ys = ys * gate_sorted[:, None].astype(ys.dtype)
    out = jnp.zeros((N, d), ys.dtype).at[tok_idx].add(ys)

    # Switch-style load-balance aux loss (fraction * probability per expert)
    probs = jax.nn.softmax(logits, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top_ids, E, dtype=jnp.float32), axis=(0, 1))
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0)) * k
    return out, aux


def _moe_ep_shard(pp: Dict, x: jax.Array, cfg: ArchConfig, *,
                  model_axis: str, capacity: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """GShard-style expert-parallel MoE body (runs under shard_map).

    Experts are sharded on the EXPERT dim over ``model_axis`` (each shard
    owns E/m complete experts); tokens are sharded over every mesh axis.
    Dispatch = capacity-bounded all-to-all (cf. ``cfg.capacity_factor``;
    overflowing (token, expert) assignments are dropped, GShard semantics);
    combine = the mirror all-to-all + gate-weighted scatter-add at origin.

    Wire bytes per device ≈ 4 · n_local · k · cf · d · dtype per layer
    (dispatch+combine, fwd+bwd) — independent of the expert count and ~16x
    less than replicated-token expert-TP at production shapes (§Perf B).
    """
    m = jax.lax.axis_size(model_axis)
    E, k = cfg.n_experts, cfg.top_k
    E_loc = E // m
    n, d = x.shape
    C = capacity

    gates, top_ids, logits = _route(pp["router"], x, k)    # router replicated
    flat_ids = top_ids.reshape(-1)                         # (n*k,)
    dest = flat_ids // E_loc                               # owning shard
    # slot within the destination bucket, first-come order (GShard priority)
    onehot = jax.nn.one_hot(dest, m, dtype=jnp.int32)      # (n*k, m)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    keep = pos < C
    slot = dest * C + pos                                  # flat send slot
    oob = m * C                                            # drop target
    slot = jnp.where(keep, slot, oob)

    tok_of_row = jnp.arange(n * k, dtype=jnp.int32) // k
    x_rows = jnp.take(x, tok_of_row, axis=0)               # (n*k, d)
    send = jnp.zeros((m * C, d), x.dtype).at[slot].set(x_rows, mode="drop")
    send_eid = jnp.zeros((m * C,), jnp.int32).at[slot].set(
        flat_ids % E_loc, mode="drop")                     # zero rows -> e0,
    #                                   harmless: zero inputs yield zero out

    recv = jax.lax.all_to_all(send.reshape(m, C, d), model_axis, 0, 0,
                              tiled=False).reshape(m * C, d)
    eids = jax.lax.all_to_all(send_eid.reshape(m, C), model_axis, 0, 0,
                              tiled=False).reshape(m * C)

    # grouped expert FFN over the received rows
    sort_idx = jnp.argsort(eids)
    rows = jnp.take(recv, sort_idx, axis=0)
    gs = jnp.bincount(eids, length=E_loc).astype(jnp.int32)
    h = _act(jax.lax.ragged_dot(rows, pp["wi_gate"], gs), cfg.act)
    h = h * jax.lax.ragged_dot(rows, pp["wi_up"], gs)
    y = jax.lax.ragged_dot(h, pp["wo"], gs)                # (m*C, d)
    y = jnp.zeros_like(y).at[sort_idx].set(y)              # unsort to slots

    back = jax.lax.all_to_all(y.reshape(m, C, d), model_axis, 0, 0,
                              tiled=False).reshape(m * C, d)
    y_rows = jnp.take(back, jnp.minimum(slot, m * C - 1), axis=0)
    y_rows = jnp.where(keep[:, None], y_rows, 0.0)
    w = (gates.reshape(-1) * keep).astype(y_rows.dtype)
    out = jnp.zeros((n, d), y_rows.dtype).at[tok_of_row].add(
        y_rows * w[:, None])

    probs = jax.nn.softmax(logits, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top_ids, E, dtype=jnp.float32),
                    axis=(0, 1))
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0)) * k
    return out, aux


def moe_apply(p: Dict, cfg: ArchConfig, x: jax.Array,
              mesh: Optional[jax.sharding.AbstractMesh] = None,
              ep: bool = False, model_axes=None) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN over (B,S,d).  Uses shard_map when a mesh is ambient so that
    routing+sort stay shard-local; single-device path otherwise."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)

    if mesh is None:
        try:
            mesh = jax.sharding.get_abstract_mesh()
        except Exception:  # pragma: no cover
            mesh = None
    names = tuple(getattr(mesh, "axis_names", ()) or ())
    routed_p = {k: v for k, v in p.items() if k != "shared"}
    # the f/expert shard axis: "model" on the production mesh, "shard" on an
    # MRA-factored mesh (where "replica" carries the batch stream)
    MX = MODEL if MODEL in names else ("shard" if "shard" in names else None)
    if model_axes is not None:                 # explicit (MRA per-tile K=1)
        MX = model_axes

    def _mx_size():
        if isinstance(MX, tuple):
            return int(np.prod([mesh.shape[a] for a in MX]))
        return mesh.shape[MX]

    if names and MX and ep and not isinstance(MX, tuple) \
            and cfg.n_experts % _mx_size() == 0:
        # expert-parallel: experts sharded on the expert dim; tokens sharded
        # over EVERY axis; capacity-bounded all-to-all dispatch (GShard)
        dp = tuple(a for a in get_batch_axes() if a in names and a != MX)
        all_axes = dp + (MX,)
        n_shards = 1
        for a in all_axes:
            n_shards *= mesh.shape[a]
        if (B * S) % n_shards == 0:
            m = mesh.shape[MX]
            n_loc = (B * S) // n_shards
            capacity = max(1, int(np.ceil(n_loc * cfg.top_k / m
                                          * cfg.capacity_factor)))
            ep_specs = {
                "router": P(None, None),
                "wi_gate": P(MX, None, None),
                "wi_up": P(MX, None, None),
                "wo": P(MX, None, None),
            }

            def ep_body(pp, xx):
                out, aux = _moe_ep_shard(pp, xx, cfg, model_axis=MX,
                                         capacity=capacity)
                aux = jax.lax.pmean(aux, all_axes)
                return out, aux

            # pin boundary shardings so GSPMD propagation outside can't
            # hand the shard_map an unnameable tiling
            routed_c = {k: jax.lax.with_sharding_constraint(v, ep_specs[k])
                        for k, v in routed_p.items()}
            xf_c = jax.lax.with_sharding_constraint(xf, P(all_axes, None))
            out, aux = jax.shard_map(
                ep_body, mesh=mesh,
                in_specs=({k: ep_specs[k] for k in routed_p},
                          P(all_axes, None)),
                out_specs=(P(all_axes, None), P()),
            )(routed_c, xf_c)
            out = out.reshape(B, S, d)
            # re-pin after the reshape: the (dp·model)-sharded token dim
            # splitting into (B, S) can otherwise leave an un-nameable tiling
            if B % (n_shards // mesh.shape[MX]) == 0:
                from repro.models.params import shard_activation
                out = shard_activation(out, DATA, None, None)
            if cfg.n_shared_experts:
                sp = p["shared"]
                gate = _act(x @ sp["wi_gate"], cfg.act)
                out = out + (gate * (x @ sp["wi_up"])) @ sp["wo"]
            return out, aux

    if names and MX:
        mx_set = set(MX) if isinstance(MX, tuple) else {MX}
        dp = tuple(a for a in get_batch_axes()
                   if a in names and a not in mx_set)
        tok = dp if dp else None
        specs = {
            "router": P(None, None),
            "wi_gate": P(None, None, MX),
            "wi_up": P(None, None, MX),
            "wo": P(None, MX, None),
        }

        def body(pp, xx):
            out, aux = _moe_ffn_local(pp, xx, cfg)
            out = jax.lax.psum(out, MX)
            aux = jax.lax.pmean(aux, MX)
            if dp:
                aux = jax.lax.pmean(aux, dp)
            return out, aux

        out, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=({k: specs[k] for k in routed_p}, P(tok, None)),
            out_specs=(P(tok, None), P()),
        )(routed_p, xf)
    else:
        out, aux = _moe_ffn_local(routed_p, xf, cfg)

    out = out.reshape(B, S, d)
    if cfg.n_shared_experts:
        sp = p["shared"]
        gate = _act(x @ sp["wi_gate"], cfg.act)
        out = out + (gate * (x @ sp["wi_up"])) @ sp["wo"]
    return out, aux
