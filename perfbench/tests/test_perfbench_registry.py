"""The harness finds configurations, traffic mixes and per-layer metrics
by file name, and BENCHMARK.json keeps to the names and units the
benchmark's contract allows."""
import json
import os
import re
import shutil

import pytest

from perfbench import harness
from perfbench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")


def _bench():
    with open(os.path.join(harness.REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units_use_only_the_allowed_characters():
    b = _bench()
    names = [c["name"] for c in b["configs"]] + \
        [w["name"] for w in b["workloads"]] + \
        [m["name"] for m in b["end_to_end"] + b["per_layer"]] + \
        [w["config"] for w in b["workloads"]] + \
        [w["traffic"] for w in b["workloads"]] + \
        [k for c in b["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for p in b["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    for text in [c["why"] for c in b["configs"]] + \
            [w["why"] for w in b["workloads"]] + \
            [m["layer"] for m in b["per_layer"]] + \
            [c["source"] for c in b["configs"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    allowed = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                  "0123456789_.-/")
    for dirpath, _, files in os.walk(os.path.join(harness.REPO_ROOT,
                                                  "perfbench")):
        if "__pycache__" in dirpath:
            continue
        rel = os.path.relpath(dirpath, harness.REPO_ROOT)
        for f in files:
            assert set(os.path.join(rel, f)) <= allowed, f


def test_every_cell_resolves_to_its_files():
    b = _bench()
    for w in b["workloads"]:
        p = harness.plan(harness.REPO_ROOT, w["name"])
        assert p.cfg["name"] == w["config"]
        assert {m["name"] for m in p.end_to_end} >= {"setup_s"}
        assert len(p.end_to_end) >= 2 and p.per_layer
        for m in p.per_layer:
            assert m["moves"] in {e["name"] for e in p.end_to_end}


def test_a_dropped_in_config_mix_and_metric_are_picked_up(tmp_path):
    """A cell added as files alone (configuration, mix, metric reader and
    the cell's own limits) runs with no edit to any file that exists."""
    root = tiny.make(str(tmp_path))
    pb = os.path.join(root, "perfbench")
    shutil.copy(os.path.join(pb, "configs", "vespa4x4-paper2.json"),
                os.path.join(pb, "configs", "dropped-cfg.json"))
    with open(os.path.join(pb, "traffic", "sweep-chunked-2m.json")) as f:
        mix = json.load(f)
    mix["topk_track"] = 8
    with open(os.path.join(pb, "traffic", "dropped-mix.json"), "w") as f:
        json.dump(mix, f)
    shutil.copy(os.path.join(pb, "limits", "sweep.islands3.json"),
                os.path.join(pb, "limits", "dropped.cell.json"))
    with open(os.path.join(pb, "metrics", "dropped_metric.py"), "w") as f:
        f.write('UNIT = "ms"\nLAYER = "sweep driver"\n'
                'MOVES = "sweep_points_per_s"\nSOURCE = "host_clock"\n\n\n'
                'def read(ctx):\n    return 1e3 * ctx.jobs[0]["wall_s"]\n')
    bench_path = os.path.join(root, "BENCHMARK.json")
    with open(bench_path) as f:
        b = json.load(f)
    b["workloads"].append({"name": "dropped.cell", "config": "dropped-cfg",
                           "traffic": "dropped-mix", "chips": 1,
                           "why": "a cell added as files alone"})
    b["end_to_end"][0]["workloads"].append("dropped.cell")
    b["per_layer"].append({"name": "dropped_metric", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "sweep driver",
                           "moves": "sweep_points_per_s",
                           "workloads": ["dropped.cell"]})
    with open(bench_path, "w") as f:
        json.dump(b, f)
    p = harness.plan(root, "dropped.cell")
    assert p.cfg["accelerators"][1]["name"] == "gsm"
    assert p.traffic["topk_track"] == 8
    assert [m["name"] for m in p.per_layer] == ["dropped_metric"]
    out = tiny.run(root, "dropped.cell")
    assert out["correct"], out["checks"]
    assert out["metrics"]["sweep_points_per_s"]["value"] > 0


def test_a_reader_that_disagrees_with_benchmark_json_is_refused(tmp_path):
    root = tiny.make(str(tmp_path))
    path = os.path.join(root, "perfbench", "metrics",
                        "device_idle.sweep.py")
    with open(path) as f:
        text = f.read()
    with open(path, "w") as f:
        f.write(text.replace('UNIT = "%"', 'UNIT = "s"'))
    with pytest.raises(ValueError, match="unit"):
        harness.plan(root, "sweep.islands3")


def test_no_chip_is_an_error_not_a_fallback():
    with pytest.raises(harness.NoChip, match="no TPU"):
        harness.look_for_chip(1, {"TPU v5 lite": {}})


def test_a_chip_missing_from_the_peaks_table_is_an_error(monkeypatch):
    import jax

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(harness.NoChip, match="TPU v9 imaginary"):
        harness.look_for_chip(1, {"TPU v5 lite": {}})
    with pytest.raises(harness.NoChip, match="needs 4 chips"):
        harness.look_for_chip(4, {"TPU v9 imaginary": {}})


def test_run_py_exits_2_without_a_chip(tmp_path):
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "perfbench/run.py", "--workload",
                        "sweep.islands3", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=harness.REPO_ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 2, r.stderr[-2000:]
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr
