"""Multi-device sharding helpers for the sweep and co-sim hot paths.

The chunked ``grid_sweep`` evaluator and the ``BatchSimEngine`` design
batch are embarrassingly parallel along one axis (flat design points,
the B design axis).  This module owns the small amount of mesh plumbing
both need to run that axis through ``jax.shard_map``:

* :func:`resolve_devices` — turn a ``devices=`` knob (``None`` / int /
  ``"auto"``) into a concrete device count, refusing more devices than
  the jax runtime exposes.  Multi-device CPU runs come from
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before the
  first jax import; the distributed tests spawn subprocesses for this).
* :func:`device_mesh` — a cached 1-D :class:`jax.sharding.Mesh` over the
  first N devices.  The cache is keyed on ``(N, axis_name)`` only — a
  bounded, device-count-indexed dict (there are at most a handful of
  distinct counts per process), never on array-backed objects, so it
  cannot grow with sweep configurations (the PR 8 cache-growth audit).
* :func:`pad_axis` / :func:`shard_len` — pad an array so an axis splits
  evenly across devices (padded tail rows are computed and discarded —
  every sharded caller slices results back to the true length).
* :func:`enable_compile_cache` — JAX's persistent compilation cache at a
  fixed path, for the entry points that run on an accelerator.

Correctness contract: sharding only *partitions* an elementwise (or
per-design-independent) computation, so any device count — including 1 —
produces identical floats; the single-device unsharded code path stays
the bit-for-bit ground truth and the sharded path is differentially
tested against it (``tests/test_shard_pallas.py``).
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Tuple, Union

import numpy as np

DEFAULT_AXIS = "shard"

# bounded by construction: one entry per (device count, axis name) pair
# actually used in this process — never keyed on arrays or configs
_MESH_CACHE: Dict[Tuple[int, str], object] = {}
_MESH_CACHE_MAX = 32

# <repo>/.jax_cache: a fixed path, so a second run of one checkout finds
# what the first one compiled
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def device_count() -> int:
    """Number of addressable jax devices (1 without XLA_FLAGS overrides)."""
    import jax
    return len(jax.devices())


def resolve_devices(devices: Union[None, int, str]) -> int:
    """Normalize a ``devices=`` knob to a concrete count.

    ``None`` -> 1 (sharding off, the ground-truth single-device path);
    ``"auto"`` -> every visible device; an int is taken as given, and
    asking for more devices than are visible raises ``ValueError``.
    """
    if devices is None:
        return 1
    n = device_count()
    if devices == "auto":
        return n
    d = int(devices)
    assert d >= 1, f"devices={devices!r}"
    if d > n:
        raise ValueError(
            f"devices={d} asked for, but jax sees only {n} device(s)")
    return d


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.  Call before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def device_mesh(n_devices: int, axis_name: str = DEFAULT_AXIS):
    """A (cached) 1-D mesh of the first ``n_devices`` devices."""
    import jax
    from jax.sharding import Mesh
    key = (int(n_devices), axis_name)
    mesh = _MESH_CACHE.get(key)
    if mesh is None:
        if len(_MESH_CACHE) >= _MESH_CACHE_MAX:    # pragma: no cover
            _MESH_CACHE.pop(next(iter(_MESH_CACHE)))
        devs = jax.devices()
        assert n_devices <= len(devs), (n_devices, len(devs))
        mesh = Mesh(np.asarray(devs[:n_devices]), (axis_name,))
        _MESH_CACHE[key] = mesh
    return mesh


def mesh_cache_size() -> int:
    """Current mesh-cache population (asserted bounded in tests)."""
    return len(_MESH_CACHE)


def shard_len(n: int, n_devices: int) -> int:
    """``n`` rounded up to a multiple of ``n_devices``."""
    return -(-n // n_devices) * n_devices


def pad_axis(a: np.ndarray, n_devices: int, axis: int = 0) -> np.ndarray:
    """Pad ``axis`` of ``a`` (edge-replicating row 0's shape class: zeros
    would do — padded rows are dropped after the gather — but repeating
    the first row keeps every lane on realistic values, avoiding
    divide-by-zero warnings inside masked expressions)."""
    n = a.shape[axis]
    target = shard_len(n, n_devices)
    if target == n:
        return a
    pad = target - n
    idx = [slice(None)] * a.ndim
    idx[axis] = slice(0, 1)
    filler = np.broadcast_to(
        a[tuple(idx)],
        a.shape[:axis] + (pad,) + a.shape[axis + 1:])
    return np.concatenate([a, filler], axis=axis)
