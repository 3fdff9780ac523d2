"""The system under test, built from a configuration file.

The only module of the benchmark that imports the program: it turns a
configuration's numbers into the program's model and workloads, and the
grid-sweep arguments of its design space.
"""
from __future__ import annotations

from perfbench import reference as ref


def model_and_workloads(cfg: dict):
    from repro.core.noc import NocConfig
    from repro.core.perfmodel import AccelWorkload, SoCPerfModel
    noc, m = cfg["noc"], cfg["model"]
    model = SoCPerfModel(
        noc=NocConfig(int(noc["rows"]), int(noc["cols"]),
                      link_bw=float(noc["link_bw"]),
                      hop_latency=float(noc["hop_latency"]),
                      max_slowdown=float(noc["max_slowdown"])),
        mem_pos=tuple(cfg["mem_pos"]), mem_service=float(m["mem_service"]),
        tg_demand=float(m["tg_demand"]),
        tg_demand_fig4=float(m["tg_demand_fig4"]),
        own_demand=float(m["own_demand"]),
        hop_latency_share=float(m["hop_latency_share"]))
    wls = [AccelWorkload(a["name"], float(a["base_mbps"]), float(a["ai"]))
           for a in cfg["accelerators"]]
    return model, wls


def sweep_kwargs(cfg: dict) -> dict:
    """``grid_sweep`` keyword arguments of the configuration's space."""
    axes = dict(ref.space_axes(cfg))
    sp = cfg["space"]
    pos = sp.get("positions")
    acc = [v for k, v in axes.items() if k.startswith("f_acc")][0]
    return {"ks": tuple(int(k) for k in sp["ks"]),
            "acc_rates": acc, "noc_rates": axes["f_noc"],
            "tg_rates": tuple(float(f) for f in sp["tg_rates"]),
            "positions": (None if pos is None
                          else tuple(tuple(int(v) for v in p) for p in pos)),
            "n_tg": int(sp["n_tg"]),
            "island_rates": sp["island_rates"]}
