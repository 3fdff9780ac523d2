"""Request traces for the co-simulation cells, made from the seed.

One general generator reads a traffic file's ``trace`` section: a
sinusoid-modulated Poisson arrival process (a day's load curve over the
run) per destination tile, at a per-tile mean rate given either as a
total split evenly (``mean_rps``) or as a share of each tile's median
service capacity over the simulated designs (``capacity_share``).
Every seed gives traces of the same length, shape and mean load; only
the draws differ.
"""
from __future__ import annotations

from typing import List

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, int(stream)])


def diurnal(per_dest_rps: np.ndarray, *, ticks: int, dt: float,
            depth: float, rng: np.random.Generator,
            period_ticks: int = 0, phase: float = 0.0) -> np.ndarray:
    """(ticks, dests) arrival counts: Poisson with rate
    ``per_dest * (1 + depth sin(2 pi t / period + phase))``."""
    if not 0.0 <= depth < 1.0:
        raise ValueError(f"depth {depth} outside [0, 1)")
    period = period_ticks or ticks
    t = np.arange(ticks, dtype=np.float64)
    mod = 1.0 + depth * np.sin(2.0 * np.pi * t / period + phase)
    lam = mod[:, None] * np.asarray(per_dest_rps, np.float64)[None, :] * dt
    return rng.poisson(lam).astype(np.float64)


def per_dest_rates(spec: dict, n_dests: int, capacity=None) -> np.ndarray:
    """Mean rate per destination tile, requests/s."""
    if "mean_rps" in spec:
        return np.full(n_dests, float(spec["mean_rps"]) / n_dests)
    share = float(spec["capacity_share"])
    return share * np.median(np.asarray(capacity), axis=0)


def pool(spec: dict, seed: int, per_dest: np.ndarray, n: int
         ) -> List[np.ndarray]:
    """``n`` traces of one seed, each from its own stream."""
    if spec["generator"] != "diurnal":
        raise ValueError(f"unknown trace generator {spec['generator']!r}")
    return [diurnal(per_dest, ticks=int(spec["ticks"]), dt=float(spec["dt"]),
                    depth=float(spec["depth"]), rng=rng_for(seed, i),
                    period_ticks=int(spec.get("period_ticks", 0)),
                    phase=float(spec.get("phase", 0.0)))
            for i in range(n)]
