"""Ahead-of-time compiles of the main path's device programs for one TPU
v5e chip that is described, not attached.

The TPU compiler refuses what the CPU interpreter accepts (block shapes
off the (8, 128) tiling, 3-D gathers, boolean selects), so these compiles
guard the fused tick kernel, the flat-point sweep evaluator and the
co-sim's device percentiles at their real sizes without a chip.  Nothing
runs: a passing compile says nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

B = 512                 # the co-sim survivor batch
T = 400
FLAT_POINTS = 2_000_000  # one sweep chunk


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shapes(arrays, sharding):
    return [jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                 sharding=sharding) for a in arrays]


def _workloads():
    from repro.configs.vespa_soc import CHSTONE
    from repro.core.perfmodel import AccelWorkload
    return [AccelWorkload(n, *CHSTONE[n]) for n in ("dfadd", "dfmul",
                                                      "dfsin")]


def _engine(variant):
    from repro.core.dfs import BatchPIDRatePolicy
    from repro.core.dse import grid_sweep
    from repro.core.perfmodel import SoCPerfModel
    from repro.sim import (BatchControllerHarness, BatchSimEngine,
                           BatchSimPlatform, SimConfig, diurnal_trace)
    m = SoCPerfModel()
    res = grid_sweep(m, _workloads(), ks=(1, 2), acc_rates=(0.2, 0.6, 1.0),
                     noc_rates=(0.5, 1.0), n_tg=2,
                     island_rates="independent")
    plat = BatchSimPlatform.from_design_points(
        m, res, np.resize(res.topk_indices(64), B), req_mb=0.002)
    ctl = None
    if variant != "open":
        ctl = BatchControllerHarness(plat.islands, plat.rates,
                                     BatchPIDRatePolicy(target=0.7),
                                     tile_names=plat.names,
                                     queue_guard_ticks=3.0)
    eng = BatchSimEngine(plat, config=SimConfig(control_interval=25),
                         controller=ctl, backend="pallas",
                         tech=16 if variant == "tech16" else None)
    return eng, diurnal_trace(2000.0, T, 3, dt=1e-3, depth=0.4, seed=5)


@pytest.mark.parametrize("variant", ["open", "pid_guard", "tech16"])
def test_tick_kernel_compiles_for_v5e(one_chip, variant):
    from repro.kernels.tick_sim import tick_kernel_call
    eng, trace = _engine(variant)
    args, _, _ = eng._pallas_args(trace)
    call, inputs, _ = tick_kernel_call(**args, interpret=False)
    compiled = jax.jit(call).lower(*_shapes(inputs, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16e9


def test_flat_point_evaluator_compiles_for_v5e(one_chip):
    """The evaluator that decodes its own points, at a chunk's size: it
    reads a few small tables and writes the three objectives."""
    from repro.core.dse import (_device_tables, _flat_point_evaluator,
                                _model_scalars, _prepare_axes)
    from repro.core.perfmodel import SoCPerfModel
    m = SoCPerfModel()
    wls = _workloads()
    lay, axes, vals = _prepare_axes(
        m, wls, (1, 2, 4), (0.2, 0.6, 1.0), (0.5, 1.0), (0.5, 1.0),
        ((1, 1), (3, 3), (0, 2)), "independent")
    tables = _device_tables(m, lay, vals)
    ev = _flat_point_evaluator(1, *_model_scalars(m, wls, 4),
                               independent=True)
    i32 = jnp.int32
    shapes = [jax.ShapeDtypeStruct((len(axes),), i32, sharding=one_chip)] * 2
    shapes += _shapes([tables], one_chip)
    compiled = ev.lower(FLAT_POINTS, *shapes).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= 3 * FLAT_POINTS * 4   # thr, e, mem
    assert mem.argument_size_in_bytes < 16 * 1024     # no per-point input


def test_certified_percentiles_compile_for_v5e(one_chip):
    """The co-sim's device percentiles at the faulted cell's size: 512
    designs, 4,000 ticks, 2 tiles, queue drops tracked."""
    from repro.sim.percentiles import _bins_jit
    hist = jax.ShapeDtypeStruct((4000, B, 2), jnp.float32, sharding=one_chip)
    compiled = _bins_jit.lower(hist, hist, hist).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes <= 16 * 1024     # (B, 3): bins, status
    assert mem.temp_size_in_bytes < 2e9
