"""Pallas kernel for the fused batched co-sim tick.

The ``lax.scan`` backend in :mod:`repro.sim.batch` lowers each simulated
tick to a dozen separate XLA ops (queue update, link-contention einsum,
service, forward coupling, power integral, control) with the ``(B, A)``
state arrays round-tripping through HBM between them.  This kernel fuses
the whole tick into ONE Pallas body:

* grid = ``(nb, T)`` with the tick dim innermost — Pallas iterates the
  last grid dim sequentially, so the per-tile simulator state (queue,
  busy, rtt, rates, guard, policy state, accumulators) lives in VMEM
  scratch across all ``T`` steps of a design block and HBM sees each
  arrival tile exactly once (the flash-attention/ssd-scan block idiom).
* per-design constants (``base``, ``req``, ``k``, ``inc``...) stream in
  as ``(bB, ...)`` blocks indexed by the design-block grid dim; the
  control-cadence flag is computed from the tick index.
* Pallas kernels cannot close over array constants ("captures constants
  ... pass them as inputs"), so every design-independent array — the
  tile→island one-hot, a vector flow demand, the forward coupling
  matrix, and the controller's island topology tables — travels through
  a replicated *extras* input group (full-shape blocks, zero index map).
* the control step is NOT reimplemented here: the caller passes the same
  ``control(rates, guard, pol_state, ctl_flag, obs)`` closure the scan
  backend uses (built by ``BatchSimEngine._jax_control``), with its
  topology constants injected back through the ``consts=`` kwarg — so
  the two fast backends share one control lowering and cannot drift.
  Guard and policy state are carried in float32 scratch and converted
  at the call boundary.

Scope matches ``backend="pallas"``: open-loop replay plus the full
controller family (membound / PID / guard / custom ``jax_step``
policies).  Faults, SLO drops, and the load balancer stay on the scan
backend.  Everything here computes in float32 (the scan backend's dtype
under jax's default x64-off config); differential tests compare against
both the scan backend (tight f32 tolerance) and the NumPy float64 engine
(looser tolerance).

The platform picks the mode (:func:`interpret_mode`): a TPU compiles the
kernel, the CPU runs it through the Pallas interpreter so the
differential suite runs everywhere.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.perfmodel import P_DYN_W, P_STATIC_W, V_BASE, V_SLOPE
from repro.sim.batch import HIGHEST
from repro.sim.observe import get_profiler

_N_IN_FIXED = 12   # arr, base, req, w, k, hop, tcr, inc, ftg, iotM,
#                    rates0, guard0


def _v2(f):
    v = V_BASE + V_SLOPE * f
    return v * v


def _tick_kernel(*refs, n_pol, n_extra, extra_keys, extra_bool,
                 pol_dtypes, control_fn, dt, own, tgd, link_bw, max_slow,
                 hop_lat, hop_share, hopf0, noc_share, n_tg, dyn_on,
                 max_q, ci, noc_idx, demand_scalar, has_fwd,
                 tech_on, t_ps, t_v0, t_v1):
    (arr_ref, base_ref, req_ref, w_ref, k_ref, hop_ref,
     tcr_ref, inc_ref, ftg_ref, iotM_ref, rates0_ref,
     guard0_ref) = refs[:_N_IN_FIXED]
    pol0_refs = refs[_N_IN_FIXED:_N_IN_FIXED + n_pol]
    e = _N_IN_FIXED + n_pol
    extra_refs = refs[e:e + n_extra]
    o = e + n_extra
    (adm_ref, served_ref, queue_ref, busy_ref, rtt_ref, ratesf_ref,
     guardf_ref, dropped_ref, energy_ref, swaps_ref) = refs[o:o + 10]
    polf_refs = refs[o + 10:o + 10 + n_pol]
    s = o + 10 + n_pol
    (q_s, b_s, rt_s, ra_s, g_s, cb_s, dr_s, en_s, sw_s, fw_s) = \
        refs[s:s + 10]
    pol_s = refs[s + 10:s + 10 + n_pol]

    ex = {}
    for key, isb, ref in zip(extra_keys, extra_bool, extra_refs):
        v = ref[...]
        ex[key] = (v > 0.5) if isb else v
    demand = ex.pop("__demand", demand_scalar)
    fwd = ex.pop("__fwd", None)

    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        q_s[...] = jnp.zeros_like(q_s)
        b_s[...] = jnp.zeros_like(b_s)
        rt_s[...] = jnp.zeros_like(rt_s)
        cb_s[...] = jnp.zeros_like(cb_s)
        dr_s[...] = jnp.zeros_like(dr_s)
        en_s[...] = jnp.zeros_like(en_s)
        sw_s[...] = jnp.zeros_like(sw_s)
        fw_s[...] = jnp.zeros_like(fw_s)
        ra_s[...] = rates0_ref[...]
        g_s[...] = guard0_ref[...]
        for p0_ref, p_s in zip(pol0_refs, pol_s):
            p_s[...] = p0_ref[...]

    rates = ra_s[...]                                       # (bB, I)
    f_tile = jnp.dot(rates, iotM_ref[...],                  # (bB, A)
                     precision=HIGHEST)
    f_noc = (rates[:, noc_idx] if noc_idx >= 0
             else jnp.ones(rates.shape[0], rates.dtype))
    fa = jnp.maximum(f_tile, 1e-3)
    fn = jnp.maximum(f_noc, 1e-3)[:, None]
    w = w_ref[...]
    hopf = 1.0 + hop_share * hop_ref[...]
    load = own + tgd * ftg_ref[...] * n_tg
    slow = jnp.maximum(1.0, load / (link_bw * fn))
    t_comp = (1.0 - w) / (k_ref[...] * fa)
    t_wire = w * slow * hopf / fn
    t_ref = (1.0 - w) + w * max(1.0, own) * hopf0

    arr_eff = arr_ref[0]                                    # (bB, A)
    if has_fwd:
        arr_eff = arr_eff + fw_s[...]
    q = q_s[...] + arr_eff
    adm = arr_eff
    if max_q != float("inf"):
        over = jnp.maximum(q - max_q, 0.0)
        q = q - over
        adm = adm - over
        dr_s[...] += over.sum(axis=-1, keepdims=True)

    busy_prev = b_s[...]
    if dyn_on:
        inc = inc_ref[...]                                  # (bB, A, L)
        # a batched contraction has no Mosaic dot lowering: multiply and
        # reduce over the tile axis instead
        loads = ((demand * busy_prev)[:, :, None] * inc).sum(axis=1)
        rho = (inc * loads[:, None, :]).max(axis=-1) / (link_bw * fn)
        r = jnp.minimum(rho, 0.999)
        dyn = jnp.minimum(1.0 + r / (2.0 * (1.0 - r)), max_slow)
    else:
        dyn = jnp.ones_like(q)
    cap = (base_ref[...] * t_ref / (t_comp + t_wire * dyn)
           / req_ref[...]) * dt
    served = jnp.minimum(q, cap)
    queue = q - served
    busy = served / cap
    rt_s[...] += hop_ref[...] * dyn * hop_lat
    if has_fwd:
        fw_s[...] = jnp.einsum("ba,aj->bj", served, fwd,
                               precision=HIGHEST)

    fnr = f_noc[:, None]                # unclamped, as the scan backend
    if tech_on:
        # physical DVFS: three baked scalars, as the scan backend
        vt = t_v0 + t_v1 * f_tile
        tp = t_ps * (P_STATIC_W + P_DYN_W * f_tile * vt * vt * busy)
        vn = t_v0 + t_v1 * fnr
        noc_p = noc_share * (
            t_ps * (P_STATIC_W + P_DYN_W * fnr * vn * vn))
    else:
        tp = P_STATIC_W + P_DYN_W * f_tile * _v2(f_tile) * busy
        noc_p = noc_share * (P_STATIC_W + P_DYN_W * fnr * _v2(fnr))
    en_s[...] += (tp.sum(axis=-1, keepdims=True) + noc_p) * dt
    ctl_busy = cb_s[...] + busy

    ctl_flag = (t + 1) % ci == 0 if ci else np.bool_(False)
    if control_fn is not None:
        t_wire_now = t_wire * dyn
        obs = {"util": ctl_busy / max(ci, 1),
               "bound": t_wire_now / (tcr_ref[...] + t_wire_now),
               "qt": queue / jnp.maximum(cap, 1e-12)}
        guard_b = g_s[...] > 0.5
        pol_state = tuple(
            (p_s[...] > 0.5) if np.issubdtype(dtp, np.bool_)
            else p_s[...]
            for p_s, dtp in zip(pol_s, pol_dtypes))
        rates, guard_b, pol_state, committed = control_fn(
            rates, guard_b, pol_state, ctl_flag, obs, consts=ex)
        sw_s[...] += jnp.where(committed, 1.0, 0.0)[:, None]
        ra_s[...] = rates
        g_s[...] = guard_b.astype(g_s.dtype)
        for p_s, ps in zip(pol_s, pol_state):
            p_s[...] = ps.astype(p_s.dtype)
    ctl_busy = jnp.where(ctl_flag, jnp.zeros_like(ctl_busy), ctl_busy)

    q_s[...] = queue
    b_s[...] = busy
    cb_s[...] = ctl_busy
    adm_ref[0] = adm
    served_ref[0] = served

    @pl.when(t == nt - 1)
    def _finish():
        queue_ref[...] = q_s[...]
        busy_ref[...] = b_s[...]
        rtt_ref[...] = rt_s[...]
        ratesf_ref[...] = ra_s[...]
        guardf_ref[...] = g_s[...]
        dropped_ref[...] = dr_s[...]
        energy_ref[...] = en_s[...]
        swaps_ref[...] = sw_s[...]
        for pf_ref, p_s in zip(polf_refs, pol_s):
            pf_ref[...] = p_s[...]


def interpret_mode() -> bool:
    """Whether the kernel runs through the Pallas interpreter, chosen from
    the platform it runs on: the CPU interprets, a TPU compiles, and any
    other platform has no lowering of this kernel."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise NotImplementedError(
        f"the fused tick kernel has no lowering for {platform!r}")


def fused_tick_sim(arrivals, consts, scalars, init, *,
                   control_fn: Optional[Callable] = None,
                   control_consts=None,
                   block_b: Optional[int] = None):
    """Run ``T`` fused simulator ticks over a ``(T, B, A)`` arrival tensor
    (see :func:`tick_kernel_call` for the arguments), compiled on a TPU
    and interpreted on the CPU.  Returns a dict of f32 outputs
    (``adm``/``served`` histories, final state, accumulators, evolved
    control state) sliced back to the true ``B``: NumPy arrays, but for
    the two histories, which stay on the device."""
    call, inputs, unpack = tick_kernel_call(
        arrivals, consts, scalars, init, control_fn=control_fn,
        control_consts=control_consts, block_b=block_b,
        interpret=interpret_mode())
    return unpack(call(*inputs))


def tick_kernel_call(arrivals, consts, scalars, init, *,
                     control_fn: Optional[Callable] = None,
                     control_consts=None,
                     block_b: Optional[int] = None,
                     interpret: bool):
    """Build the tick kernel's ``pallas_call`` without running it.

    ``consts``: per-design arrays — ``base``/``req``/``w``/``k``/``hop``/
    ``tcr`` ``(B, A)``, ``inc`` ``(B, A, L)``, ``ftg`` ``(B, 1)``.
    ``scalars``: python-level model/config constants (baked into the
    kernel), including ``iot``/``noc_idx``/``demand``/``forward`` and the
    control cadence ``ci`` (control runs on ticks ``t`` with
    ``(t + 1) % ci == 0``).  ``init``: ``rates``/``guard`` ``(B, I)`` plus
    a ``pol`` tuple of B-leading 2-D policy-state arrays.
    ``control_consts``: the numpy topology tables the control lowering
    needs (re-injected through its ``consts=`` kwarg; required when
    ``control_fn`` is set).

    Returns ``(call, inputs, unpack)``: ``call(*inputs)`` runs the kernel
    and ``unpack`` turns its outputs into :func:`fused_tick_sim`'s dict.
    """
    arrivals = np.asarray(arrivals, dtype=np.float32)
    T, B, A = arrivals.shape
    I = init["rates"].shape[1]
    bB = int(block_b) if block_b else min(B, 128)
    Bp = -(-B // bB) * bB
    pol0 = tuple(np.asarray(p) for p in init["pol"])
    pol_dtypes = tuple(p.dtype for p in pol0)
    for p in pol0:
        assert p.ndim == 2 and p.shape[0] == B, (
            "policy state arrays must be 2-D and B-leading; got "
            f"{p.shape}")

    def padded(a, axis=0):
        a = np.asarray(a, dtype=np.float32)
        if Bp == B:
            return a
        reps = [1] * a.ndim
        idx = [slice(None)] * a.ndim
        idx[axis] = slice(0, 1)
        reps[axis] = Bp - B
        return np.concatenate([a, np.tile(a[tuple(idx)], reps)],
                              axis=axis)

    iot = np.asarray(scalars["iot"])
    iotM = np.zeros((I, A), dtype=np.float32)               # island→tile
    iotM[iot, np.arange(A)] = 1.0

    # extras: design-independent arrays replicated to every block (Pallas
    # forbids captured array constants)
    extra_np = []                                           # (key, arr, bool)
    if np.ndim(scalars["demand"]) > 0:
        extra_np.append(("__demand",
                         np.asarray(scalars["demand"], np.float32), False))
    fwd = scalars.get("forward")
    if fwd is not None:
        extra_np.append(("__fwd", np.asarray(fwd, np.float32), False))
    if control_fn is not None:
        assert control_consts is not None, \
            "control_fn requires its topology tables (control_consts)"
        for key in sorted(control_consts):
            a = np.asarray(control_consts[key])
            extra_np.append((key, a.astype(np.float32),
                             np.issubdtype(a.dtype, np.bool_)))

    inputs = [
        padded(arrivals, axis=1),
        padded(consts["base"]), padded(consts["req"]),
        padded(consts["w"]), padded(consts["k"]),
        padded(consts["hop"]), padded(consts["tcr"]),
        padded(consts["inc"]), padded(consts["ftg"]),
        iotM,
        padded(init["rates"]), padded(init["guard"]),
    ] + [padded(p) for p in pol0] + [a for _, a, _ in extra_np]
    L = int(consts["inc"].shape[-1])
    nb = Bp // bB

    def blk(shape, imap):
        return pl.BlockSpec(shape, imap)

    def full_blk(a):
        nd = a.ndim
        return blk(a.shape, lambda b, t, nd=nd: (0,) * nd)

    in_specs = [
        blk((1, bB, A), lambda b, t: (t, b, 0)),        # arr
    ] + [blk((bB, A), lambda b, t: (b, 0))] * 6 + [     # base..tcr
        blk((bB, A, L), lambda b, t: (b, 0, 0)),        # inc
        blk((bB, 1), lambda b, t: (b, 0)),              # ftg
        blk((I, A), lambda b, t: (0, 0)),               # iotM
        blk((bB, I), lambda b, t: (b, 0)),              # rates0
        blk((bB, I), lambda b, t: (b, 0)),              # guard0
    ] + [blk((bB, p.shape[1]), lambda b, t: (b, 0)) for p in pol0] \
      + [full_blk(a) for _, a, _ in extra_np]

    out_specs = [
        blk((1, bB, A), lambda b, t: (t, b, 0)),        # adm
        blk((1, bB, A), lambda b, t: (t, b, 0)),        # served
        blk((bB, A), lambda b, t: (b, 0)),              # queue
        blk((bB, A), lambda b, t: (b, 0)),              # busy
        blk((bB, A), lambda b, t: (b, 0)),              # rtt
        blk((bB, I), lambda b, t: (b, 0)),              # rates
        blk((bB, I), lambda b, t: (b, 0)),              # guard
        blk((bB, 1), lambda b, t: (b, 0)),              # dropped
        blk((bB, 1), lambda b, t: (b, 0)),              # energy
        blk((bB, 1), lambda b, t: (b, 0)),              # swaps
    ] + [blk((bB, p.shape[1]), lambda b, t: (b, 0)) for p in pol0]
    out_shape = [
        jax.ShapeDtypeStruct((T, Bp, A), jnp.float32),
        jax.ShapeDtypeStruct((T, Bp, A), jnp.float32),
    ] + [jax.ShapeDtypeStruct((Bp, A), jnp.float32)] * 3 + [
        jax.ShapeDtypeStruct((Bp, I), jnp.float32),
        jax.ShapeDtypeStruct((Bp, I), jnp.float32),
    ] + [jax.ShapeDtypeStruct((Bp, 1), jnp.float32)] * 3 + [
        jax.ShapeDtypeStruct((Bp, p.shape[1]), jnp.float32)
        for p in pol0]
    scratch = ([pltpu.VMEM((bB, A), jnp.float32)] * 3       # q, busy, rtt
               + [pltpu.VMEM((bB, I), jnp.float32)] * 2     # rates, guard
               + [pltpu.VMEM((bB, A), jnp.float32)]         # ctl_busy
               + [pltpu.VMEM((bB, 1), jnp.float32)] * 3     # dr, en, sw
               + [pltpu.VMEM((bB, A), jnp.float32)]         # fwd carry
               + [pltpu.VMEM((bB, p.shape[1]), jnp.float32)
                  for p in pol0])

    kernel = functools.partial(
        _tick_kernel, n_pol=len(pol0), n_extra=len(extra_np),
        extra_keys=tuple(k for k, _, _ in extra_np),
        extra_bool=tuple(bl for _, _, bl in extra_np),
        pol_dtypes=pol_dtypes, control_fn=control_fn,
        dt=float(scalars["dt"]), own=float(scalars["own"]),
        tgd=float(scalars["tgd"]), link_bw=float(scalars["link_bw"]),
        max_slow=float(scalars["max_slow"]),
        hop_lat=float(scalars["hop_lat"]),
        hop_share=float(scalars["hop_share"]),
        hopf0=float(scalars["hopf0"]),
        noc_share=float(scalars["noc_share"]),
        n_tg=float(scalars["n_tg"]), dyn_on=bool(scalars["dyn_on"]),
        max_q=float(scalars["max_q"]), ci=int(scalars["ci"]),
        noc_idx=int(scalars["noc_idx"]),
        demand_scalar=(float(scalars["demand"])
                       if np.ndim(scalars["demand"]) == 0 else None),
        has_fwd=fwd is not None,
        tech_on=bool(scalars.get("tech_on", False)),
        t_ps=float(scalars.get("t_ps", 1.0)),
        t_v0=float(scalars.get("t_v0", V_BASE)),
        t_v1=float(scalars.get("t_v1", V_SLOPE)))
    call = pl.pallas_call(
        kernel, grid=(nb, T), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret)
    get_profiler().count("tick_loop_builds")

    def unpack(outs):
        (adm, served, queue, busy, rtt, rates, guard, dropped, energy,
         swaps) = outs[:10]
        polF = tuple(
            (np.asarray(p)[:B] > 0.5) if np.issubdtype(dtp, np.bool_)
            else np.asarray(p)[:B].astype(dtp)
            for p, dtp in zip(outs[10:], pol_dtypes))
        return {
            "adm": adm[:, :B],              # left on the device
            "served": served[:, :B],
            "queue": np.asarray(queue)[:B],
            "busy": np.asarray(busy)[:B],
            "rtt": np.asarray(rtt)[:B],
            "rates": np.asarray(rates)[:B],
            "guard": np.asarray(guard)[:B] > 0.5,
            "dropped": np.asarray(dropped)[:B, 0],
            "energy": np.asarray(energy)[:B, 0],
            "swaps": np.asarray(swaps)[:B, 0],
            "pol": polF,
        }

    return call, inputs, unpack
