"""The trace reduction and the roofline counts, on the CPU.

``data/small_trace.xplane.pb`` was recorded on a TPU v5e: three runs of
one jitted elementwise program (one ``multiply_add_fusion`` op of about
28 us each) inside a ``bench.job`` annotation, each run in a
``bench.step`` annotation and followed by a 2 ms sleep.
"""
import os

import numpy as np
import pytest

from perfbench import counts, traces

SMALL = os.path.join(os.path.dirname(__file__), "data",
                     "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    return traces.load_planes(SMALL)


def test_op_names_lose_their_numbering():
    assert traces.op_name("%divide_reduce_fusion.2 = (f32[8]) fusion(x)") \
        == "divide_reduce_fusion"
    assert traces.op_name("%while.3 = (s32[]) while(t)") == "while"
    assert traces.op_name("jit_run_scan(13966638236751832978)") \
        == "jit_run_scan"
    assert traces.op_name("%tpu_custom_call.1 = (f32[4]) custom-call()") \
        == "tpu_custom_call"


def test_union_merges_overlaps_and_keeps_gaps():
    spans = np.array([[5.0, 6.0], [0.0, 2.0], [1.0, 3.0], [3.0, 4.0]])
    np.testing.assert_array_equal(traces.union(spans),
                                  [[0.0, 4.0], [5.0, 6.0]])
    assert traces.union(np.zeros((0, 2))).shape == (0, 2)


def test_reduction_of_a_recorded_chip_trace(planes):
    win = traces.window_of(planes, "bench.job")
    assert win is not None
    red = traces.reduce_trace(planes, win)
    assert red["devices"] == 1
    # three runs of the one fusion, 28.16 + 28.345 + 28.315 us
    assert red["busy_s"] == pytest.approx(84.82e-6, rel=1e-3)
    assert set(red["ops"]) == {"multiply_add_fusion"}
    assert traces.op_seconds(red, "multiply_add_fusion") == \
        pytest.approx(red["busy_s"])
    assert traces.module_seconds(red, "jit__lambda") == \
        pytest.approx(84.83e-6, rel=1e-3)
    assert red["window_s"] == pytest.approx(9.48063e-3, rel=1e-4)
    gaps = dict(red["idle_gaps"])
    # the three sleeps after the steps are the long idle gaps
    assert max(gaps, key=gaps.get) == "$time sleep"
    assert gaps["$time sleep"] > 6.5e-3
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)


def test_window_clips_device_time(planes):
    win = traces.window_of(planes, "bench.job")
    # only the first run lies in the first 0.5 ms of the job
    red = traces.reduce_trace(planes, (win[0], win[0] + 0.5e-3))
    assert red["busy_s"] == pytest.approx(28.16e-6, rel=1e-3)


def test_evaluator_count_by_hand():
    # one point, one accelerator: reads K, f_acc, hops, f_noc, f_tg and
    # writes throughput, energy, memory traffic: 8 float32 = 32 bytes
    one = counts.evaluator(1, 1)
    assert one["bytes"] == 32
    assert one["flops"] == 44
    # the islands space: 3 accelerators, 56 bytes a point
    assert counts.evaluator(10, 3)["bytes"] == 560


def test_tick_loop_count_by_hand():
    # 2 ticks, 1 design, 1 tile, 2 links, 2 islands, 3 ladder levels,
    # control every tick: per tick 32 (tile) + 2 * 4 (links) = 40 flops;
    # 2 control steps of 2 islands at 20 + 3 * 4 = 32 flops each
    w = counts.tick_loop(2, 1, 1, 2, 2, levels=3, control_interval=1,
                         faults=False, histories=2)
    assert w["flops"] == 2 * 40 + 2 * 2 * 32
    # reads the (2, 1) trace, writes two (2, 1, 1) histories, reads the
    # design's constants: incidence 2, eight per-tile arrays, four
    # per-island arrays of 2
    assert w["bytes"] == 4 * (2 + 2 * 2 + (2 + 8 + 8))
    assert counts.mesh_links(4, 4) == 48


def test_least_time_names_its_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_time({"flops": 50.0, "bytes": 10.0}, peak) == \
        {"seconds": 1.0, "bound": "memory"}
    assert counts.least_time({"flops": 500.0, "bytes": 10.0}, peak) == \
        {"seconds": 5.0, "bound": "compute"}


def test_scan_and_kernel_are_held_to_one_count():
    """The two tick-loop rooflines differ only in where they read the
    device time: on the same time they give the same share."""
    import types
    from perfbench import harness
    kernel = harness.load_reader(harness.REPO_ROOT, "tick_kernel_roofline")
    scan = harness.load_reader(harness.REPO_ROOT, "tick_scan_roofline")
    shape = {"ticks": 4000, "designs": 512, "tiles": 3, "links": 48,
             "islands": 4, "levels": 19, "control_interval": 25,
             "faults": False, "histories": 2}
    red = {"ops": {kernel.KERNEL_OP: 0.2}, "modules": {scan.SCAN_MODULE: 0.2}}
    ctx = types.SimpleNamespace(trace=red, traced=[{}, {}], shape=shape,
                                peak={"flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9})
    a, b = kernel.read(ctx), scan.read(ctx)
    assert a == pytest.approx(b)
    least = counts.least_time(counts.tick_loop(**shape), ctx.peak)
    assert least["bound"] == "memory"
    assert a == pytest.approx(100 * 2 * least["seconds"] / 0.2)
