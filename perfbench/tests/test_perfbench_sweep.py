"""The sweep cell's ``correct`` on the CPU, at the small size of
:mod:`tiny`: sound runs pass; the bfloat16 control and each fault the
timed path can have fail."""
import numpy as np
import pytest

from perfbench import compare, harness
from perfbench import reference as ref
from perfbench.tests import tiny

CELL = "sweep.islands3"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(str(tmp_path_factory.mktemp("tiny")))


def _limits(root):
    return harness.plan(root, CELL).limits


def test_sound_run_is_correct(root):
    out = tiny.run(root, CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1
    c = out["checks"]
    assert c["count_err"]["value"] == 0 and c["front_gap"]["value"] == 0
    assert 0 < c["value_err"]["value"] < 1e-6


def test_bfloat16_control_fails(root):
    p = harness.plan(root, CELL)
    k = int(p.traffic["topk_track"])
    want = ref.sweep(p.cfg, k)
    low = ref.sweep(p.cfg, k, ref.BF16)
    got = {key: low[key] for key in ("n_points", "n_valid", "pareto",
                                     "topk", "indices", "values")}
    judged = compare.judge(compare.sweep_numbers(p.cfg, got, want) |
                           {"sweeps_differ": 0.0}, p.limits)
    assert not all(j["ok"] for j in judged.values()), judged
    assert judged["value_err"]["value"] > 100 * p.limits["value_err"]


def _wrap_evaluator(monkeypatch, alter):
    import jax.numpy as jnp
    from repro.core import dse
    orig = dse._flat_point_evaluator

    def patched(*args, **kw):
        fn = orig(*args, **kw)

        def broken(*a):
            thr, energy, mem = fn(*a)
            return alter(jnp, thr, energy, mem)
        return broken
    monkeypatch.setattr(dse, "_flat_point_evaluator", patched)


def test_an_answer_altered_where_produced_fails(root, monkeypatch):
    def alter(jnp, thr, energy, mem):
        # every seventh design of a chunk reads 0.1% faster than it is
        hit = jnp.arange(thr.shape[0]) % 7 == 3
        return jnp.where(hit, thr * 1.001, thr), energy, mem
    _wrap_evaluator(monkeypatch, alter)
    out = tiny.run(root, CELL)
    assert not out["correct"]
    assert out["checks"]["value_err"]["value"] > 5e-4


def test_half_the_batch_left_out_fails(root, monkeypatch):
    def alter(jnp, thr, energy, mem):
        half = thr.shape[0] // 2
        keep = jnp.arange(thr.shape[0]) < half
        return (jnp.where(keep, thr, 0.0), jnp.where(keep, energy, 1e30),
                jnp.where(keep, mem, 0.0))
    _wrap_evaluator(monkeypatch, alter)
    out = tiny.run(root, CELL)
    assert not out["correct"]
    assert out["checks"]["topk_gap"]["value"] > 1e-3


def test_a_merge_that_returns_its_state_unchanged_fails(root, monkeypatch):
    from repro.core import dse
    monkeypatch.setattr(dse, "_merge_front", lambda cand, rows: cand)
    out = tiny.run(root, CELL)
    assert not out["correct"]
    assert out["checks"]["front_gap"]["value"] > 1.0
