"""Request-weighted p50/p99 latencies of B designs on the device,
certified equal to the host's float64 answer.

The tick loops of the ``jax`` and ``pallas`` backends leave their
``(T, B, A)`` float32 ``admitted``/``served`` (and ``queue_drops``)
histories on the device.  :func:`certified_bins` dispatches one jitted
program over all B designs that finds each design's p50 and p99 latency
bin there and *certifies* it: a bin is certified only where the host's
float64 :func:`~repro.sim.engine.latency_percentiles` provably picks the
same one.  :func:`percentiles_from_bins` turns the certified bins into
latencies and recomputes every other design with ``latency_percentiles``,
the one reference, counting them under the profiler counter
``percentile_host_fallbacks``.

What is reproduced, per design and tile (float64 on the host, on the
float32 values upcast, which loses nothing)::

    ca = cumsum(n)       cs = cumsum(served + queue_drops)
    mid = ca - n/2       depart = searchsorted(cs, mid, "left")
    done = (depart < T) & (n > 0)      bin k = depart - t, value (k + 1/2) dt

and the q-th request-weighted percentile is the value of the first bin
whose cumulative weight C(k) reaches (q/100) W, W the weight done.

How it is certified:

* every sum is a double-float (hi, lo) pair of float32 numbers built with
  error-free transformations (TwoSum): each addition errs by less than
  3u^2 + 13u^3 (u = 2^-24) of its nonnegative inputs, and no value passes
  through more than about log2(n) + 8 of them, so ``_DEV`` (2^-38) bounds
  the device's error with room to spare, where float32 alone would give
  2^-24 per addition.  The host's float64 error over ``n`` additions is
  below ``n 2^-53`` of the inputs, counted here as ``n 2^-52``.  A
  decision is certain only where it clears the sum of both bounds;
* departures come from a bitonic merge of the two monotone curves of each
  tile; a sample is *uncertain* where ``mid`` lies within those bounds of
  ``cs[depart - 1]`` or ``cs[depart]``;
* a percentile is certified where its target clears both edges of the
  chosen bin by the bounds plus U, the weight of the design's uncertain
  samples: each of them could land in any bin, or in none.

Exact ties are never certified.  Neither is a design with a negative,
non-finite or subnormal history entry (the TPU flushes subnormals).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.sim.engine import latency_percentiles
from repro.sim.observe import get_profiler

FALLBACK_COUNTER = "percentile_host_fallbacks"

# status codes of the device's answer, per design
UNCERTIFIED, CERTIFIED, NO_SAMPLES = 0, 1, 2

_DEV = 2.0 ** -38               # device error bound, relative to the inputs
_ABS = 2.0 ** -110              # absolute slack: subnormal intermediates
_TINY_BITS = int(np.float32(2.0 ** -100).view(np.int32))
_INF_BITS = int(np.float32(np.inf).view(np.int32))
_LOW12 = -4096                  # int32 mask clearing 12 low mantissa bits


# -- double-float arithmetic ---------------------------------------------

def _two_sum(a, b):
    """(s, e) with s = fl(a + b) and s + e = a + b exactly."""
    s = a + b
    v = s - a
    return s, (a - (s - v)) + (b - v)


def _fast_two_sum(a, b):
    s = a + b
    return s, b - (s - a)


def _add(x, y):
    """Double-float sum, relative error below 3u^2 + 13u^3 (u = 2^-24;
    Joldes, Muller and Popescu 2017, "AccurateDWPlusDW")."""
    sh, sl = _two_sum(x[0], y[0])
    th, tl = _two_sum(x[1], y[1])
    vh, vl = _fast_two_sum(sh, sl + th)
    return _fast_two_sum(vh, tl + vl)


def _neg(x):
    return -x[0], -x[1]


def _split12(x):
    """x = hi + lo exactly, hi holding x's 12 leading significant bits."""
    hi = lax.bitcast_convert_type(
        lax.bitcast_convert_type(x, jnp.int32) & _LOW12, jnp.float32)
    return hi, x - hi


def _const_split(q: float) -> Tuple[np.float32, np.float32, np.float32]:
    """A float64 constant as 12 + 12 leading bits and a float32 rest."""
    def trunc(v):
        return np.float32((np.float32(v).view(np.int32)
                           & np.int32(_LOW12)).view(np.float32))
    qh = trunc(q)
    qm = trunc(q - float(qh))
    return qh, qm, np.float32(q - float(qh) - float(qm))


def _scale(x, q: float):
    """Double-float x times the float64 constant q: the four 12-bit by
    12-bit products are exact, the rest is below 2^-46 of the product."""
    qh, qm, ql = _const_split(q)
    xh, xl = _split12(x[0])
    out = (xh * qh, jnp.zeros_like(xh))
    for term in (xh * qm, xl * qh, xl * qm, x[0] * ql,
                 x[1] * np.float32(q)):
        out = _add(out, (term, jnp.zeros_like(term)))
    return out


def _gt(a, b):
    """Lexicographic a > b over tuples of arrays."""
    out = a[-1] > b[-1]
    for x, y in zip(a[-2::-1], b[-2::-1]):
        out = (x > y) | ((x == y) & out)
    return out


def _lexmax(a, b):
    take = _gt(a, b)
    return tuple(jnp.where(take, x, y) for x, y in zip(a, b))


def _lexmin(a, b):
    take = _gt(b, a)
    return tuple(jnp.where(take, x, y) for x, y in zip(a, b))


def _shift(x, s, fill, reverse: bool):
    """x moved ``s`` (traced) places along its last axis, ``fill`` coming
    in."""
    pos = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    if reverse:
        return jnp.where(pos < x.shape[-1] - s, jnp.roll(x, -s, axis=-1),
                         fill)
    return jnp.where(pos >= s, jnp.roll(x, s, axis=-1), fill)


def _scan(x, op, fill, reverse: bool = False):
    """Inclusive prefix of ``op`` along the last axis (Hillis-Steele:
    every output passes through at most log2(n) applications).  The
    steps are a loop: fused into one expression, each step's two reads
    of the last would grow the code exponentially with the depth."""
    def step(i, x):
        s = jnp.left_shift(1, i)
        return op(tuple(_shift(v, s, f, reverse) for v, f in zip(x, fill)),
                  x)
    return lax.fori_loop(0, (x[0].shape[-1] - 1).bit_length(), step, x)


def _tree(x):
    """Double-float sum of f32 ``x`` over its last axis (a power of two),
    pairwise: log2(n) additions deep."""
    n = x.shape[-1]
    if n == 1:
        return x[..., 0], jnp.zeros_like(x[..., 0])
    v = _two_sum(x[..., :n // 2], x[..., n // 2:])
    while v[0].shape[-1] > 1:
        h = v[0].shape[-1] // 2
        v = _add((v[0][..., :h], v[1][..., :h]),
                 (v[0][..., h:], v[1][..., h:]))
    return v[0][..., 0], v[1][..., 0]


def _total(x):
    """Double-float sum over the last two axes (A, n)."""
    hi, lo = _tree(x)
    out = (hi[..., 0], lo[..., 0])
    for a in range(1, hi.shape[-1]):
        out = _add(out, (hi[..., a], lo[..., a]))
    return out


def _merge(keys, payload):
    """Bitonic merge along the last axis (length a power of two) of rows
    that ascend then descend in the strict lexicographic order of
    ``keys``; ``payload`` travels with them.  A loop, as in :func:`_scan`."""
    n = keys[0].shape[-1]
    pos = lax.broadcasted_iota(jnp.int32, keys[0].shape, keys[0].ndim - 1)

    def stage(i, ops):
        h = jnp.right_shift(n // 2, i)
        lower = (pos & h) == 0
        partner = tuple(jnp.where(lower, jnp.roll(v, -h, axis=-1),
                                  jnp.roll(v, h, axis=-1)) for v in ops)
        swap = _gt(ops[:len(keys)], partner[:len(keys)]) == lower
        return tuple(jnp.where(swap, p, v) for v, p in zip(ops, partner))
    return lax.fori_loop(0, (n - 1).bit_length(), stage,
                         tuple(keys) + tuple(payload))


# -- the device program ----------------------------------------------------

def _bins(adm, srv, drp):
    """(B, 3) int32: p50 bin, p99 bin and status of each design."""
    T, B, A = adm.shape
    P = 1 << (T - 1).bit_length()       # the smallest power of two >= T
    f32, i32 = jnp.float32, jnp.int32
    hists = (adm, srv) if drp is None else (adm, srv, drp)

    valid = jnp.ones((B,), bool)
    for h in hists:
        bits = lax.bitcast_convert_type(h, i32)
        good = (bits == 0) | ((bits >= _TINY_BITS) & (bits < _INF_BITS))
        valid = valid & jnp.all(good, axis=(0, 2))

    def rows(x):                        # (T, B, A) -> (B * A, T)
        return jnp.transpose(x, (1, 2, 0)).reshape(B * A, T)

    n = rows(adm)
    zero = jnp.zeros_like(n)
    exits = (rows(srv), zero) if drp is None else _two_sum(rows(srv),
                                                           rows(drp))
    ca = _scan((n, zero), _add, (0.0, 0.0))
    cs = _scan(exits, _add, (0.0, 0.0))
    mid = _add(ca, (-0.5 * n, zero))
    # the exact curves never descend; make their approximations ascend
    # too (moving no value past its bound), so that the merge is sound
    ninf = (-jnp.inf, -jnp.inf)
    mid = _scan(mid, _lexmax, ninf)
    cs = _scan(cs, _lexmax, ninf)
    # what either side's arithmetic can move a comparison of mid with cs
    tot = (ca[0][:, -1:] + cs[0][:, -1:]) * f32(1 + 2.0 ** -20)
    margin = tot * f32(2 * _DEV + (T + 8) * 2.0 ** -52) + f32(_ABS)

    # mids ascending, +inf pads | +inf pads, exits descending
    R, pad = B * A, P - T
    t_ = jnp.arange(T, dtype=i32)
    p_ = jnp.arange(pad, dtype=i32)
    infs = jnp.full((R, pad), jnp.inf, f32)
    zpad = jnp.zeros((R, pad), f32)
    hi = jnp.concatenate([mid[0], infs, infs, cs[0][:, ::-1]], axis=-1)
    lo = jnp.concatenate([mid[1], zpad, zpad, cs[1][:, ::-1]], axis=-1)
    idx = jnp.broadcast_to(jnp.concatenate(
        [t_, 2 * T + p_, 2 * T + pad + p_[::-1], T + t_[::-1]]), hi.shape)
    wt = jnp.concatenate([n, zpad, zpad, zero], axis=-1)
    hi, lo, idx, wt = _merge((hi, lo, idx), (wt,))

    pos = lax.broadcasted_iota(i32, hi.shape, 1)
    is_mid = idx < T
    is_exit = (idx >= T) & (idx < 2 * T)
    d = pos - idx                       # at a mid: the exits before it
    k = d - idx
    prev = _scan((jnp.where(is_exit, hi, -jnp.inf),
                  jnp.where(is_exit, lo, -jnp.inf)), _lexmax, ninf)
    nxt = _scan((jnp.where(is_exit, hi, jnp.inf),
                 jnp.where(is_exit, lo, jnp.inf)), _lexmin,
                (jnp.inf, jnp.inf), reverse=True)
    gap_prev = _add((hi, lo), _neg(prev))[0]
    gap_next = _add(nxt, _neg((hi, lo)))[0]
    sure = (((d == 0) | (gap_prev > margin))
            & ((d == T) | (gap_next > margin)))
    live = is_mid & (wt > 0)
    w = jnp.where(live & (d < T), wt, 0.0).reshape(B, A, 2 * P)
    u = jnp.where(live & ~sure, wt, 0.0).reshape(B, A, 2 * P)
    k = k.reshape(B, A, 2 * P)

    # U's float32 sum, rounded up past its own error
    U = jnp.sum(u, axis=(1, 2)) * f32(1 + A * 2 * P * 2.0 ** -23)
    W = _total(w)
    target = tuple(jnp.stack(v, axis=-1)
                   for v in zip(_scale(W, 0.5), _scale(W, 0.99)))

    def weight_upto(kk):                # (B, 2) bins -> C(kk), (B, 2)
        m = k[:, None] <= kk[:, :, None, None]
        return _total(jnp.where(m, w[:, None], 0.0))

    def reaches(kk):
        return _add(weight_upto(kk), _neg(target))

    def step(_, lohi):
        lo_k, hi_k = lohi
        mid_k = (lo_k + hi_k) >> 1
        d_hi, d_lo = reaches(mid_k)
        up = (d_hi > 0) | ((d_hi == 0) & (d_lo >= 0))
        return jnp.where(up, lo_k, mid_k), jnp.where(up, mid_k, hi_k)

    # C(-T) = 0 < target <= W = C(T - 1) wherever anything is done
    start = (jnp.full((B, 2), -T, i32), jnp.full((B, 2), T - 1, i32))
    _, kq = lax.fori_loop(0, (2 * T - 2).bit_length(), step, start)

    slack = (U[:, None] + W[0][:, None] * f32(
        (2 * _DEV + (A * T + 4) * 2.0 ** -52) * (1 + 2.0 ** -20))
        + f32(_ABS))
    clear = (reaches(kq)[0] > slack) & (-reaches(kq - 1)[0] > slack)
    status = jnp.where(
        ~valid, UNCERTIFIED,
        jnp.where(W[0] == 0,
                  jnp.where(U == 0, NO_SAMPLES, UNCERTIFIED),
                  jnp.where(jnp.all(clear, axis=1), CERTIFIED,
                            UNCERTIFIED)))
    return jnp.concatenate([kq, status[:, None].astype(i32)], axis=1)


_bins_jit = jax.jit(_bins)


# -- host side -------------------------------------------------------------

def certified_bins(admitted, served, queue_drops=None):
    """Dispatch the device program on the tick loop's device-resident
    ``(T, B, A)`` float32 histories; returns the pending ``(B, 3)``
    answer, or ``None`` where there is nothing the device can take
    (host arrays, another dtype, no ticks)."""
    hists = [h for h in (admitted, served, queue_drops) if h is not None]
    if not all(isinstance(h, jax.Array) and h.dtype == jnp.float32
               for h in hists) or admitted.shape[0] == 0:
        return None
    return _bins_jit(admitted, served, queue_drops)


def percentiles_from_bins(bins, admitted, served, dt: float,
                          queue_drops=None) -> Tuple[np.ndarray, np.ndarray]:
    """(p50, p99), ``(B,)`` each, from the device's answer; designs it did
    not certify are recomputed here from the ``(T, B, A)`` host
    histories with :func:`latency_percentiles`."""
    B = admitted.shape[1]
    out = np.asarray(bins)[:B]
    vals = (out[:, :2].astype(np.float64) + 0.5) * dt
    vals[out[:, 2] == NO_SAMPLES] = np.nan
    redo = np.flatnonzero(out[:, 2] == UNCERTIFIED)
    for b in redo:
        vals[b] = latency_percentiles(
            admitted[:, b], served[:, b], dt,
            queue_drops=None if queue_drops is None else queue_drops[:, b])
    get_profiler().count(FALLBACK_COUNTER, len(redo))
    return vals[:, 0].copy(), vals[:, 1].copy()
