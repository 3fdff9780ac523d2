"""The sweep evaluator decodes its own points on the device.

``grid_sweep(devices=N)`` hands the evaluator a chunk's first coordinates
and small value tables; the evaluator decodes every point from an iota
and gathers its axis values in float32.  Contracts:

* the device-decoded evaluator returns ``(thr, energy, mem)`` bitwise
  equal to the same objective math fed host-decoded float32 inputs (the
  arrays the host used to upload), and ``grid_sweep`` built on it gives
  bitwise the front, top-k, candidate values, dense arrays and
  ``n_valid`` of a sweep built from those host-decoded inputs — for
  shared and independent rates, with and without the tech axis, chunked
  and one-shot, on 1 and 4 (virtual) devices;
* the decode is exact past 2**31 points, where no int32 can hold a
  global flat index;
* the executable depends on the point count and the layout, never on
  how many values an axis has: a cut space compiles what the whole space
  runs;
* each evaluator call books its points under the counter
  ``sweep_points_decoded_on_device``.
"""
import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.configs.vespa_soc import CHSTONE
from repro.core import dse
from repro.core.perfmodel import AccelWorkload, SoCPerfModel

WLS = tuple(AccelWorkload(n, *CHSTONE[n]) for n in ("dfadd", "dfmul",
                                                      "dfsin"))
# 1,458-point chunks of the independent space: not a multiple of 4, so
# the four-device evaluator pads
SPACE = dict(ks=(1, 2, 4), acc_rates=(0.2, 0.6, 1.0),
             noc_rates=(0.5, 1.0), tg_rates=(0.5, 1.0),
             positions=((1, 1), (3, 3), (0, 2)), n_tg=4)
CHUNK = 1500
TOPK = 16
OBJS = ("throughput", "area", "energy_per_unit", "mem_traffic")
CASES = [(rates, tech, path) for rates in ("independent", "shared")
         for tech in (False, True) for path in ("chunked", "one_shot")]
HERE = os.path.dirname(os.path.abspath(__file__))


def _space(rates, tech):
    kw = dict(SPACE, island_rates=rates)
    if tech:
        kw["tech_node"] = (16, 45)
    return kw


def _host_inputs(model, lay, vals, shape, lo, hi):
    """The evaluator's inputs of points ``[lo, hi)`` decoded on the host
    and cast to float32, as the host used to upload them."""
    coords = np.unravel_index(np.arange(lo, hi), shape)
    A = lay.A
    f32 = functools.partial(np.asarray, dtype=np.float32)
    kA = f32([vals["k"][coords[lay.k(a)]] for a in range(A)])
    faA = f32([vals["acc"][a][coords[lay.fa(a)]] for a in range(A)])
    hopA = f32([model.hop_counts(pos_idx=vals["pos"][coords[lay.pos(a)]])
                for a in range(A)])
    tech = (tuple(f32(vals[n][coords[lay.tdim]])
                  for n in ("tech_ps", "tech_v0", "tech_v1"))
            if lay.tech else None)
    return (kA, faA, hopA, f32(vals["noc"][coords[lay.fnoc]]),
            f32(vals["tg"][coords[lay.ftg]]), tech)


def _host_decoded_sweep(model, kw):
    """A dense sweep whose float objectives come from host-decoded
    float32 inputs through :func:`dse._objectives`, and whose area and
    validity come from :func:`dse._eval_grid`'s float64 host math."""
    import jax
    lay, axes, vals = dse._prepare_axes(
        model, WLS, kw["ks"], kw["acc_rates"], kw["noc_rates"],
        kw["tg_rates"], kw["positions"], kw["island_rates"],
        tech_node=kw.get("tech_node"))
    shape = tuple(len(v) for _, v in axes)
    n = int(np.prod(shape))
    math = jax.jit(functools.partial(
        dse._objectives, *dse._model_scalars(model, WLS, kw["n_tg"])))
    thr, energy, mem = (np.asarray(o, dtype=np.float64) for o in
                        math(*_host_inputs(model, lay, vals, shape, 0, n)))
    host = dse._eval_grid(model, WLS, kw["n_tg"], lay, vals,
                          lambda dim, v: dse._axis(v, dim, len(shape)),
                          shape)
    return dse.SweepResult(
        axes=axes, shape=shape, workloads=WLS, n_tg=kw["n_tg"],
        throughput=thr, area=host["area"].ravel(), energy_per_unit=energy,
        valid=host["valid"].ravel(), mem_traffic=mem)


def check_case(rates, tech, path, devices):
    """Raise AssertionError unless the device decode reproduces the host
    decode bitwise, for the evaluator and for ``grid_sweep``."""
    import jax
    from repro import shard
    model = SoCPerfModel()
    kw = _space(rates, tech)
    lay, axes, vals = dse._prepare_axes(
        model, WLS, kw["ks"], kw["acc_rates"], kw["noc_rates"],
        kw["tg_rates"], kw["positions"], rates,
        tech_node=kw.get("tech_node"))
    shape = tuple(len(v) for _, v in axes)
    n = int(np.prod(shape))

    # the evaluator on one chunk, away from the space's start
    lo, hi = n // 3 + 7, n // 3 + 7 + CHUNK
    scalars = dse._model_scalars(model, WLS, kw["n_tg"])
    want = jax.jit(functools.partial(dse._objectives, *scalars))(
        *_host_inputs(model, lay, vals, shape, lo, hi))
    ev = dse._flat_point_evaluator(devices, *scalars, tech=tech,
                                   independent=rates == "independent")
    got = ev(shard.shard_len(hi - lo, devices),
             np.asarray(np.unravel_index(lo, shape), dtype=np.int32),
             np.asarray(shape, dtype=np.int32),
             dse._device_tables(model, lay, vals))
    for name, w, g in zip(("thr", "energy", "mem"), want, got):
        assert np.array_equal(np.asarray(w), np.asarray(g)[:hi - lo]), name

    # the whole sweep against one built from host-decoded inputs
    ref = _host_decoded_sweep(model, kw)
    res = dse.grid_sweep(model, WLS, devices=devices, topk_track=TOPK,
                         chunk_points=CHUNK if path == "chunked" else None,
                         **kw)
    assert res.n_valid == ref.n_valid
    if path == "one_shot":
        for o in OBJS + ("valid",):
            assert np.array_equal(getattr(res, o), getattr(ref, o)), o
        return
    assert res.n_chunks > 1
    assert np.array_equal(res.pareto, ref.pareto_indices())
    for o, maximize in dse._TRACKED_OBJECTIVES:
        assert np.array_equal(res.topk[o],
                              ref.topk_indices(TOPK, o, maximize)), o
        assert np.array_equal(res.cand_values[o],
                              ref.objective_values(o, res.cand_indices)), o


CHILD = """
import json, sys, traceback
sys.path[:0] = [sys.argv[1], sys.argv[2]]
import jax
assert len(jax.devices()) == 4, jax.devices()
import test_dse_device_decode as t
out = {}
for case in t.CASES:
    try:
        t.check_case(*case, devices=4)
        out["-".join(map(str, case))] = "ok"
    except AssertionError:
        out["-".join(map(str, case))] = traceback.format_exc()[-2000:]
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    """Every case on four virtual CPU devices, in one child process (the
    device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", CHILD,
                        os.path.join(HERE, "..", "src"), HERE],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("rates,tech,path", CASES)
def test_device_decode_matches_host_decode(request, rates, tech, path,
                                           devices):
    if devices == 1:
        check_case(rates, tech, path, devices)
    else:
        out = request.getfixturevalue("four_devices")
        assert out["-".join(map(str, (rates, tech, path)))] == "ok", out


# an 11-axis space of 2,524,344,750 points, past int32
BIG = (3, 3, 3, 19, 9, 9, 9, 2, 15, 15, 15)
BIG_N = int(np.prod(BIG, dtype=np.int64))
SPAN = 50_000


@pytest.mark.parametrize("lo", [2 ** 31, 2 ** 31 + 123_457,
                                BIG_N - SPAN])
def test_decode_past_2_31_points(lo):
    import jax
    import jax.numpy as jnp
    assert BIG_N > 2 ** 31
    decode = jax.jit(lambda start, sizes: dse._decode_digits(
        jnp.arange(SPAN, dtype=jnp.int32), start, sizes))
    got = decode(np.asarray(np.unravel_index(lo, BIG), dtype=np.int32),
                 np.asarray(BIG, dtype=np.int32))
    want = np.unravel_index(np.arange(lo, lo + SPAN, dtype=np.int64), BIG)
    for d, (g, w) in enumerate(zip(got, want)):
        assert np.array_equal(np.asarray(g), w), d


@pytest.mark.parametrize("n", [1, 2, 3, 7, 19, 1_000, 65_537,
                               2 ** 29 + 11])
def test_divmod_is_exact(n):
    """The float32 quotient, corrected, is exact for every q < 2**30:
    at and around multiples of n, and at the range's ends."""
    import jax
    k = np.arange(0, (2 ** 30 - 1) // n + 1, max(1, (2 ** 30 // n) // 4096),
                  dtype=np.int64)
    q = np.unique(np.clip(np.concatenate([k * n - 1, k * n, k * n + 1,
                                          [2 ** 30 - 1]]),
                          0, 2 ** 30 - 1)).astype(np.int32)
    d, r = jax.jit(dse._divmod)(q, np.full_like(q, n))
    assert np.array_equal(np.asarray(d), q // n)
    assert np.array_equal(np.asarray(r), q % n)


def test_cut_space_compiles_nothing_new():
    """A ``ks=[1]`` sweep, then a ``ks=[1, 2, 4]`` sweep with the same
    chunk sizes: the second compiles nothing (the tables are data)."""
    import jax
    m = SoCPerfModel()
    # n_tg 3 is this test's own, so its first sweep has to compile
    kw = dict(SPACE, island_rates="independent", devices=1, topk_track=8,
              positions=((1, 1), (0, 2), (2, 2), (3, 1)), n_tg=3)
    inner = int(np.prod([2, 3, 3, 3, 2, 4, 4, 4]))   # all but the K axes
    events = []

    def log(event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(log)
    try:
        cut = dse.grid_sweep(m, WLS, chunk_points=inner,
                             **{**kw, "ks": (1,)})
        assert len(cut) == inner
        assert any(e.endswith("backend_compile_duration") for e in events)
        assert any(e.endswith("jaxpr_trace_duration") for e in events)
        events.clear()
        whole = dse.grid_sweep(m, WLS, chunk_points=inner, **kw)
        assert whole.n_chunks == 27
    finally:
        jax.monitoring.unregister_event_duration_listener(log)
    assert events == []


@pytest.mark.parametrize("devices", [1, None])
@pytest.mark.parametrize("chunk_points", [CHUNK, None])
def test_points_decoded_on_device_counter(devices, chunk_points):
    from repro.sim.observe import get_profiler, reset_profiler
    reset_profiler()
    res = dse.grid_sweep(SoCPerfModel(), WLS, devices=devices,
                         chunk_points=chunk_points,
                         **_space("independent", False))
    counts = get_profiler().counts
    if devices is None:
        assert "sweep_points_decoded_on_device" not in counts
    else:
        assert counts["sweep_points_decoded_on_device"] == len(res)
