"""Dry-run spec builders + DFS energy policy + SSM long-context decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import AbstractMesh
import repro.core as C
from repro.configs import get_config
from repro.configs.base import LM_SHAPES, ShapeConfig
from repro.launch import specs as SP
from repro.models.layers import AttnOptions
from repro.models.transformer import LM


def _mesh11():
    # a 1-device mesh with the production axis NAMES exercises all spec
    # logic (divisibility checks treat size-1 axes as always divisible)
    return jax.make_mesh((1, 1), ("data", "model"))


def test_cache_shardings_cover_every_leaf():
    mesh = _mesh11()
    for arch in ("granite-8b", "deepseek-v2-lite-16b", "mamba2-370m",
                 "zamba2-7b"):
        cfg = get_config(arch)
        lm = LM(cfg, opts=AttnOptions(backend="naive"), remat=False)
        cache_abs, tok = SP.abstract_decode_inputs(
            lm, ShapeConfig("d", 256, 4, "decode"))
        sh = SP.cache_shardings(lm, cache_abs, mesh)
        n_abs = len(jax.tree_util.tree_leaves(cache_abs))
        n_sh = len(jax.tree_util.tree_leaves(
            sh, is_leaf=lambda x: isinstance(x, jax.sharding.NamedSharding)))
        assert n_abs == n_sh, (arch, n_abs, n_sh)


def test_batch_shardings_fallback_drops_trailing_axes():
    """global_batch < product(batch axes) must fall back, never replicate
    silently (the multi-pod FSDP regression)."""
    mesh = AbstractMesh((2, 2, 2), ("pod", "data", "model"))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 8), jnp.int32)}
    sh = SP.batch_shardings(batch, mesh, extra=("model",))
    spec = sh["tokens"].spec
    # 4 % 8 != 0 -> drop "model": (pod, data) = 4-way fits exactly
    assert spec[0] == ("pod", "data")


def test_param_shardings_respect_divisibility():
    mesh = AbstractMesh((1, 2), ("data", "model"))
    cfg = get_config("phi3-medium-14b")       # kv = 10 heads
    lm = LM(cfg, opts=AttnOptions(backend="naive"), remat=False)
    sh = SP.param_shardings(lm, mesh)
    # flattened kv dim 10*128=1280 divides 2 -> sharded
    assert sh["blocks"]["attn"]["wk"].spec[2] == "model"
    # norm scales replicated
    assert sh["final_norm"].spec == jax.sharding.PartitionSpec(None,)


def test_energy_policy_derates_within_throughput_budget():
    cfg = get_config("granite-8b")
    plan = C.default_plan(cfg)
    islands = C.default_islands(plan)
    tel = {t.name: C.TileTelemetry(1.0, 0, 0, 0, 0.9) for t in plan.tiles}

    def perf_eval(rates):
        # toy model: throughput set by noc_mem; power sums islands
        tps = 100.0 * rates.get("noc_mem", 1.0)
        watts = sum(C.chip_power(r, 1.0) for r in rates.values())
        return tps, watts

    best = C.policy_energy_per_token(islands, tel, perf_eval)
    tps, _ = perf_eval(best)
    base_tps, _ = perf_eval({k: 1.0 for k in best})
    assert tps >= 0.98 * base_tps              # throughput constraint held
    # at least one non-bottleneck island was derated
    assert any(v < 1.0 for k, v in best.items() if k != "noc_mem")


def test_ssm_long_decode_past_window():
    """Mamba2 decode is O(1): decoding 3x past the 'cache length' works and
    matches the full forward (no window to evict)."""
    cfg = get_config("mamba2-370m").reduced()
    lm = LM(cfg, opts=AttnOptions(backend="naive"), remat=False)
    params = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        lm.init(jax.random.PRNGKey(0)))
    B, S = 1, 97
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    full, _ = lm.forward(params, tokens=toks)
    _, cache = lm.prefill(params, tokens=toks[:, :32])
    cache = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if hasattr(a, "dtype")
        and a.dtype == jnp.bfloat16 else a, cache)
    for t in range(32, S):
        lg, cache = lm.decode_step(params, cache, tokens=toks[:, t:t + 1])
    scale = float(jnp.max(jnp.abs(full)))
    err = float(jnp.max(jnp.abs(lg - full[:, -1]))) / scale
    assert err < 1e-3, err


def test_hbm_model_moe_decode_reads_full_weights():
    from repro.launch.costing import hbm_bytes
    cfg = get_config("deepseek-v2-lite-16b")
    dec = hbm_bytes(cfg, LM_SHAPES["decode_32k"])
    # batch 128 x top-6 >> 64 experts: the sweep reads ~all weights
    assert dec > cfg.n_params() * 2


def test_mra_k_scales_weight_reads():
    from repro.launch.costing import hbm_bytes
    cfg = get_config("deepseek-v2-lite-16b")
    b1 = hbm_bytes(cfg, LM_SHAPES["decode_32k"], mra_k=1)
    b4 = hbm_bytes(cfg, LM_SHAPES["decode_32k"], mra_k=4)
    assert b4 > b1                              # the paper's area cost
    assert b4 - b1 == pytest.approx(3 * cfg.n_params() * 2, rel=0.01)
