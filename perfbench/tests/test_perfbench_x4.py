"""A four-chip sweep cell, added to the small copy as files alone (its
mix, its limits and its entry, as a later PR would add it), on four
virtual CPU devices at the small size of :mod:`tiny`: a sound run is
correct; one whose shards are not gathered from the other devices is
not.  Each run is a child process, since the device count is fixed when
JAX starts."""
import json
import os
import subprocess
import sys

import pytest

from perfbench.tests import tiny

CHILD = r"""
import json, shutil, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
from perfbench.tests import tiny
root = tiny.make(sys.argv[2], devices=4)
pb = root + "/perfbench"
# a four-chip cell added as files alone: its mix, its limits, its entry
with open(pb + "/traffic/sweep-chunked-2m.json") as f:
    mix = json.load(f)
mix["devices"] = 4
with open(pb + "/traffic/sweep-chunked-2m-x4.json", "w") as f:
    json.dump(mix, f)
shutil.copy(pb + "/limits/sweep.islands3.json",
            pb + "/limits/sweep.islands3.x4.json")
with open(root + "/BENCHMARK.json") as f:
    bench = json.load(f)
bench["workloads"].append({"name": "sweep.islands3.x4", "chips": 4,
                           "config": "vespa4x4-chstone3",
                           "traffic": "sweep-chunked-2m-x4",
                           "why": "the sweep sharded over four chips"})
for m in bench["end_to_end"] + bench["per_layer"]:
    if "sweep.islands3" in m.get("workloads", ()):
        m["workloads"].append("sweep.islands3.x4")
with open(root + "/BENCHMARK.json", "w") as f:
    json.dump(bench, f)
if sys.argv[3] == "exchange":
    import jax.numpy as jnp
    from repro.core import dse
    orig = dse._flat_point_evaluator

    def patched(n_devices, *a, **kw):
        fn = orig(n_devices, *a, **kw)

        def broken(*args):
            # only the first device's shard of each output comes back
            return tuple(jnp.where(jnp.arange(o.shape[0])
                                   < o.shape[0] // n_devices, o, 0.0)
                         for o in fn(*args))
        return broken
    dse._flat_point_evaluator = patched
out = tiny.run(root, "sweep.islands3.x4")
print(json.dumps({"correct": out["correct"], "checks": out["checks"],
                  "devices": out["device"]}))
"""


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("exchange", False)])
def test_four_device_sweep(tmp_path, fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", CHILD, tiny.REPO,
                        str(tmp_path), fault], env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["correct"] is correct, out["checks"]
