import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init); everything else in this module assumes 512 host
placeholder devices standing in for 2 pods x 256 chips.

For each cell this produces, from the compiled artifact:
  * memory_analysis()      — proof the cell fits per-device HBM,
  * cost_analysis()        — HLO FLOPs / bytes for §Roofline,
  * collective wire bytes  — parsed from the partitioned HLO text
                             (all-reduce / all-gather / reduce-scatter /
                              all-to-all / collective-permute),
and writes one JSON per cell under experiments/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
"""
import argparse
import json
import re
import time
import traceback
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED_ARCHS, get_config, shapes_for
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.replication import make_mra_mesh
from repro.core.tiles import default_plan
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh
from repro.models.layers import AttnOptions
from repro.models.params import abstract_params
from repro.models.transformer import LM
from repro.optim import adamw
from repro.runtime.train import TrainConfig, make_train_step

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

from repro.launch.costing import (collective_stats, flops_of_jaxpr,
                                  hbm_bytes)

# ---------------------------------------------------------------------------
# Cell construction
# ---------------------------------------------------------------------------


from dataclasses import dataclass, field as _field
from repro.models.params import set_batch_axes, get_batch_axes


@dataclass(frozen=True)
class CellOptions:
    """One §Perf design point for a cell.

    strategy: 'tp' (paper-faithful baseline: 16-way tensor parallel over
    the model axis), 'fsdp' (batch also sharded over the model axis ->
    GSPMD gathers weights per layer instead of all-reducing activations),
    'mra<K>' (Vespa C1: K-factored mesh, replicated tiles, stream split
    over the replica axis).
    """
    strategy: str = "tp"
    folded: bool = False           # folded-triangle causal schedule
    onehot_loss: bool = False      # vocab-parallel gold extraction
    grad_rs: bool = False          # bf16 grads + reduce-scatter to shards
    kv_int8: bool = False          # quantized decode cache (MLA)
    remat: bool = True
    accum: int = 1
    q_block: int = 512

    @property
    def ep(self) -> bool:
        return "ep" in re.split(r"[-_]", self.strategy)

    @property
    def mra_k(self) -> int:
        m = re.search(r"mra(\d+)", self.strategy)
        return int(m.group(1)) if m else 0

    @property
    def mra_attn_only(self) -> bool:
        return "attn" in self.strategy

    def tag(self) -> str:
        parts = [self.strategy]
        if self.folded:
            parts.append("folded")
        if self.onehot_loss:
            parts.append("vploss")
        if self.grad_rs:
            parts.append("gradrs")
        if self.kv_int8:
            parts.append("kvint8")
        if not self.remat:
            parts.append("noremat")
        if self.accum > 1:
            parts.append(f"acc{self.accum}")
        return "-".join(parts)


def build_lm(cfg: ArchConfig, co: CellOptions, mesh=None, plan=None) -> LM:
    opts = AttnOptions(backend="chunked", q_block=co.q_block,
                       kv_block=co.q_block, folded=co.folded)
    block_pspecs = None
    if co.grad_rs and mesh is not None:
        # per-layer use-site constraints: stacked specs minus the layer dim
        from jax.sharding import PartitionSpec as _P
        from repro.core.replication import merged_rules
        from repro.models.params import pspecs_for
        lm0 = LM(cfg, opts=opts, remat=co.remat)
        stacked = pspecs_for(lm0.param_specs(),
                             merged_rules(plan or default_plan(cfg), mesh),
                             mesh)["blocks"]
        block_pspecs = jax.tree_util.tree_map(
            lambda ps: _P(*tuple(ps)[1:]), stacked,
            is_leaf=lambda x: isinstance(x, _P))
    moe_axes = None
    if co.mra_k and co.mra_attn_only:
        moe_axes = ("replica", "shard")     # experts keep full 16-way TP
    kv_dtype = jnp.int8 if co.kv_int8 else None
    return LM(cfg, opts=opts, remat=co.remat, onehot_loss=co.onehot_loss,
              moe_ep=co.ep, moe_axes=moe_axes, kv_cache_dtype=kv_dtype,
              block_pspecs=block_pspecs)


def make_cell_mesh(co: CellOptions, multi_pod: bool):
    if co.mra_k:
        return make_mra_mesh(co.mra_k, multi_pod=multi_pod)
    return make_production_mesh(multi_pod=multi_pod)


def lower_cell(arch: str, shape_name: str, mesh, *,
               co: CellOptions = CellOptions()):
    """Returns (lowered, meta) for one cell on the given mesh."""
    cfg = get_config(arch)
    shape = shapes_for(cfg)[shape_name]
    plan = default_plan(cfg)
    if co.mra_k:
        kinds = (("attn", "shared_attn") if co.mra_attn_only
                 else ("attn", "ffn", "moe", "ssm", "shared_attn"))
        for t in plan.tiles:
            if t.kind in kinds:
                plan = plan.with_replication(t.name, co.mra_k)
    lm = build_lm(cfg, co, mesh=mesh, plan=plan)
    rules_override = {"experts": "model", "expert_ff": None} if co.ep else None
    param_sh = SP.param_shardings(lm, mesh, plan, rules_override)
    params_abs = abstract_params(lm.param_specs())

    extra = ("model",) if "fsdp" in re.split(r"[-_]", co.strategy) else ()
    prev_axes = get_batch_axes()
    batch_axes = tuple(a for a in ("pod", "data", "replica") + extra
                       if a in mesh.axis_names)
    set_batch_axes(batch_axes)
    try:
        if shape.kind == "train":
            tc = TrainConfig(accum=co.accum,
                             grad_reduce_dtype="bf16" if co.grad_rs else "")
            gps = None
            if co.grad_rs:
                from repro.core.replication import merged_rules
                from repro.models.params import pspecs_for
                gps = pspecs_for(lm.param_specs(),
                                 merged_rules(plan, mesh), mesh)
            step = make_train_step(lm, plan, mesh, tc, grad_pspecs=gps)
            opt_abs = SP.abstract_opt_state(params_abs)
            batch_abs = SP.abstract_batch(cfg, shape)
            ctr_abs = SP.abstract_counters(plan)
            in_sh = (param_sh, SP.opt_shardings(param_sh, mesh),
                     SP.batch_shardings(batch_abs, mesh, extra),
                     SP.counter_shardings(ctr_abs, mesh))
            fn = jax.jit(step, in_shardings=in_sh, donate_argnums=(0, 1, 3))
            with jax.set_mesh(mesh):
                lowered = fn.lower(params_abs, opt_abs, batch_abs, ctr_abs)
        elif shape.kind == "prefill":
            tok_abs = SP.abstract_prefill_tokens(shape)
            fn = jax.jit(lambda p, t: lm.prefill(p, tokens=t),
                         in_shardings=(param_sh,
                                       SP.batch_shardings(tok_abs, mesh,
                                                          extra)))
            with jax.set_mesh(mesh):
                lowered = fn.lower(params_abs, tok_abs)
        else:  # decode
            cache_abs, tok_abs = SP.abstract_decode_inputs(lm, shape)
            cache_sh = SP.cache_shardings(lm, cache_abs, mesh)
            fn = jax.jit(lambda p, c, t: lm.decode_step(p, c, tokens=t),
                         in_shardings=(param_sh, cache_sh,
                                       SP.batch_shardings(tok_abs, mesh)),
                         donate_argnums=(1,))
            with jax.set_mesh(mesh):
                lowered = fn.lower(params_abs, cache_abs, tok_abs)

        meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
                "mesh": dict(mesh.shape), "n_params": cfg.n_params(),
                "n_active_params": cfg.n_active_params(),
                "strategy": co.tag(),
                "tokens": shape.global_batch * (shape.seq_len
                                                if shape.kind != "decode"
                                                else 1)}
        # scan-aware total FLOPs from the jaxpr (cost_analysis counts loop
        # bodies once — see launch/costing.py) + analytic HBM traffic
        meta["jaxpr_flops_total"] = _jaxpr_flops_for(lm, plan, cfg, shape,
                                                     accum=co.accum)
        meta["hbm_bytes_total"] = hbm_bytes(cfg, shape,
                                            mra_k=max(co.mra_k, 1),
                                            kv_int8=co.kv_int8)
        meta["mra_k"] = max(co.mra_k, 1)
    finally:
        set_batch_axes(prev_axes)
    return lowered, meta


def _jaxpr_flops_for(lm, plan, cfg, shape, *, accum: int = 1) -> float:
    """Trace the same step abstractly (no mesh needed) and count FLOPs."""
    import dataclasses as _dc
    lm = _dc.replace(lm, block_pspecs=None)    # constraints need a mesh
    params_abs = abstract_params(lm.param_specs())
    if shape.kind == "train":
        step = make_train_step(lm, plan, None, TrainConfig(accum=accum))
        args = (params_abs, SP.abstract_opt_state(params_abs),
                SP.abstract_batch(cfg, shape),
                SP.abstract_counters(default_plan(cfg)))
        jx = jax.make_jaxpr(step)(*args)
    elif shape.kind == "prefill":
        jx = jax.make_jaxpr(lambda p, t: lm.prefill(p, tokens=t))(
            params_abs, SP.abstract_prefill_tokens(shape))
    else:
        cache_abs, tok_abs = SP.abstract_decode_inputs(lm, shape)
        jx = jax.make_jaxpr(lambda p, c, t: lm.decode_step(p, c, tokens=t))(
            params_abs, cache_abs, tok_abs)
    return flops_of_jaxpr(jx.jaxpr)


def analyze(lowered, meta, *, parse_collectives: bool = True) -> Dict[str, Any]:
    t0 = time.monotonic()
    compiled = lowered.compile()
    compile_s = time.monotonic() - t0
    out = dict(meta)
    out["compile_seconds"] = round(compile_s, 2)
    chips = int(np.prod(list(meta["mesh"].values())))
    out["chips"] = chips

    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        # NOTE body-once: XLA counts while-loop bodies a single time, so
        # these two under-report for scan-over-layers models; the roofline
        # uses jaxpr_flops_total / hbm_bytes_total instead (costing.py).
        out["hlo_flops_per_device_bodyonce"] = float(ca.get("flops", 0.0))
        out["hlo_bytes_per_device_bodyonce"] = float(
            ca.get("bytes accessed", 0.0))
    except Exception as e:                              # pragma: no cover
        out["cost_analysis_error"] = repr(e)

    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                v = getattr(ma, k, None)
                if v is not None:
                    out[k] = int(v)
    except Exception as e:                              # pragma: no cover
        out["memory_analysis_error"] = repr(e)

    if parse_collectives:
        try:
            txt = compiled.as_text()
            out.update(collective_stats(txt, default_group=chips))
            out["hlo_chars"] = len(txt)
        except Exception as e:                          # pragma: no cover
            out["collective_parse_error"] = repr(e)
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             co: CellOptions = CellOptions(),
             save: bool = True) -> Dict[str, Any]:
    mesh = make_cell_mesh(co, multi_pod)
    t0 = time.monotonic()
    lowered, meta = lower_cell(arch, shape_name, mesh, co=co)
    meta["lower_seconds"] = round(time.monotonic() - t0, 2)
    meta["multi_pod"] = multi_pod
    meta["folded"] = co.folded
    res = analyze(lowered, meta)
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        tag = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
        if co.tag() != "tp":
            tag += "__" + co.tag()
        with open(os.path.join(OUT_DIR, tag + ".json"), "w") as f:
            json.dump(res, f, indent=1, sort_keys=True)
    return res


def iter_cells():
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        for shape_name in shapes_for(cfg):
            yield arch, shape_name


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--folded", action="store_true")
    ap.add_argument("--onehot-loss", action="store_true")
    ap.add_argument("--strategy", default="tp")
    ap.add_argument("--grad-rs", action="store_true")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--accum", type=int, default=1)
    args = ap.parse_args()
    co = CellOptions(strategy=args.strategy, folded=args.folded,
                     onehot_loss=args.onehot_loss, grad_rs=args.grad_rs,
                     kv_int8=args.kv_int8,
                     remat=not args.no_remat, accum=args.accum)

    pods = []
    if args.multi_pod or not args.single_pod:
        pods.append(True)
    if args.single_pod or not args.multi_pod:
        pods.append(False)
    pods = sorted(set(pods))       # False (single) first

    cells = list(iter_cells()) if args.all else [(args.arch, args.shape)]
    failures = []
    for arch, shape_name in cells:
        for mp in pods:
            tag = f"{arch} x {shape_name} x {'2-pod(512)' if mp else '1-pod(256)'}"
            try:
                r = run_cell(arch, shape_name, multi_pod=mp, co=co)
                print(f"OK   {tag}: compile={r['compile_seconds']}s "
                      f"flops={r.get('jaxpr_flops_total', 0):.3e} "
                      f"coll={r.get('collective_bytes', 0):.3e}B", flush=True)
            except Exception as e:
                failures.append((tag, repr(e)))
                print(f"FAIL {tag}: {e!r}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES")
        raise SystemExit(1)
    print("\nALL CELLS PASSED")


if __name__ == "__main__":
    main()
