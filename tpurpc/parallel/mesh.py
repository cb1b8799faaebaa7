"""Device-mesh construction and sharding helpers.

The reference's only parallelism is RPC-plane (many connections / pollers,
SURVEY.md §2.7); serving sharded models behind those connections is the TPU
side of the capability. One mesh, five logical axes:

=====  =====================================================================
axis   meaning
=====  =====================================================================
dp     data parallel — batch sharding, gradient psum
pp     pipeline parallel — layer stages, microbatch ppermute ring
sp     sequence parallel — long-context ring attention (K/V rotate over ICI)
tp     tensor parallel — Megatron-style column/row splits, activation psum
ep     expert parallel — MoE all_to_all dispatch/return
=====  =====================================================================

Axes the hardware can't fill get size 1 — the collectives still compile and
the same program scales when real chips arrive (pjit/XLA semantics: axis size
is a compile-time constant, not a code path).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def shard_map(f, *, mesh, in_specs, out_specs, check_rep: bool = False):
    """``jax.shard_map`` with replication checking off unless asked for
    (``check_rep`` is what jax now calls ``check_vma``)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)

AXES = ("dp", "pp", "sp", "tp", "ep")


def factor_mesh(n_devices: int,
                priority: Sequence[str] = ("dp", "tp", "sp", "pp", "ep"),
                caps: Optional[Dict[str, int]] = None) -> Dict[str, int]:
    """Split ``n_devices`` over the five logical axes.

    Greedy: peel prime factors (largest first) onto axes in ``priority``
    round-robin, respecting per-axis ``caps``. Deterministic, total product
    == n_devices, unfilled axes get 1.
    """
    sizes = {a: 1 for a in AXES}
    caps = caps or {}
    rem = n_devices
    factors = []
    d = 2
    while d * d <= rem:
        while rem % d == 0:
            factors.append(d)
            rem //= d
        d += 1
    if rem > 1:
        factors.append(rem)
    factors.sort(reverse=True)
    i = 0
    for f in factors:
        for _ in range(len(priority)):
            a = priority[i % len(priority)]
            i += 1
            if sizes[a] * f <= caps.get(a, n_devices):
                sizes[a] *= f
                break
        else:  # no axis can take it (all capped) — dump on dp
            sizes["dp"] *= f
    return sizes


def build_mesh(n_devices: Optional[int] = None,
               sizes: Optional[Dict[str, int]] = None,
               devices: Optional[Sequence] = None) -> Mesh:
    """An ``AXES``-named mesh over the first ``n_devices`` jax devices."""
    devs = list(devices) if devices is not None else list(jax.devices())
    n = n_devices or len(devs)
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    sizes = dict(sizes) if sizes else factor_mesh(n)
    for a in AXES:
        sizes.setdefault(a, 1)
    shape = tuple(sizes[a] for a in AXES)
    if math.prod(shape) != n:
        raise ValueError(f"mesh sizes {sizes} != {n} devices")
    arr = np.asarray(devs[:n]).reshape(shape)
    return Mesh(arr, AXES)


def shard(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def axis_size(mesh: Mesh, axis: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
