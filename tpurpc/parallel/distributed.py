"""Multi-host bring-up: the control-plane seam for scaling past one host.

The reference's multi-node story is MPI-launched processes whose data
plane rides verbs (SURVEY.md §2.8); tpurpc's TPU-native equivalent is
``jax.distributed`` — one process per host joins a coordinator, and after
that the SAME pjit/mesh programs used single-host (tpurpc/parallel/mesh.py,
models/transformer.py) run globally: XLA routes collectives over ICI
inside a slice and DCN between slices. The RPC plane (this package's
host-level transport) is unchanged — it is how requests REACH a host;
the mesh is how work spreads across chips once there.

Axis placement rule (the scaling-book recipe): put ``dp`` (and ``pp``)
outermost so their collectives are the ones that cross DCN — they move
gradients/activations once per step; keep ``tp``/``sp``/``ep`` inside a
slice where ICI bandwidth lives. ``factor_mesh`` already orders axes this
way; ``global_mesh`` just applies it to the multi-host device list.

Env UX (mirrors the reference's launcher-agnostic env family):
``TPURPC_COORDINATOR`` (host:port), ``TPURPC_NUM_PROCESSES``,
``TPURPC_PROCESS_ID``. With none of those set the call is a single-process
no-op (the same program runs on a lone host); set ``TPURPC_AUTODETECT=1``
to instead let jax's own cluster autodetection (GKE/Cloud TPU metadata)
do the join — opt-in because on a plain host it would block hunting for a
coordinator that doesn't exist.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

_initialized = False


def initialize_cluster(coordinator: Optional[str] = None,
                       num_processes: Optional[int] = None,
                       process_id: Optional[int] = None) -> int:
    """Join (or stand alone as) a jax.distributed cluster; returns the
    process index. Single-process (num_processes in (None on a lone host,
    1)) is a no-op so the same program runs anywhere. Idempotent."""
    global _initialized
    import jax

    coordinator = coordinator or os.environ.get("TPURPC_COORDINATOR")
    if num_processes is None:
        env = os.environ.get("TPURPC_NUM_PROCESSES")
        num_processes = int(env) if env else None
    if process_id is None:
        env = os.environ.get("TPURPC_PROCESS_ID")
        process_id = int(env) if env else None

    if _initialized:
        return jax.process_index()
    autodetect = os.environ.get("TPURPC_AUTODETECT") == "1"
    if (coordinator is None and not autodetect
            and (num_processes is None or num_processes == 1)):
        _initialized = True  # single-process: nothing to join
        return 0
    # Cross-process collectives on the CPU backend need an explicit
    # implementation (on TPU the ICI/DCN fabric is implicit) — and the
    # CPU backend is in play whenever JAX_PLATFORMS is unset (default
    # fallback), "cpu", or lists cpu, so set it for every multi-process
    # join: the knob only affects the CPU client and is harmless on TPU.
    # TPURPC_CPU_COLLECTIVES selects the implementation (gloo | mpi).
    # Must run before the first backend touch. (CI exercises this with
    # no TPU pod: tests/test_distributed.py.)
    impl = os.environ.get("TPURPC_CPU_COLLECTIVES", "gloo")
    try:
        jax.config.update("jax_cpu_collectives_implementation", impl)
    except ValueError:
        if "TPURPC_CPU_COLLECTIVES" in os.environ:
            raise  # an explicitly-set bad value must fail loudly
    if autodetect and coordinator is None:
        jax.distributed.initialize()  # cluster env (GKE/Cloud TPU) fills in
    else:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    _initialized = True
    return jax.process_index()


def global_mesh(sizes: Optional[Dict[str, int]] = None):
    """A 5-axis mesh over every device in the cluster (all processes).

    With ``sizes`` omitted, ``factor_mesh`` factors the GLOBAL device
    count with dp outermost — so the axes most tolerant of DCN hops are
    the ones that cross hosts. Call after :func:`initialize_cluster`."""
    import jax

    from tpurpc.parallel.mesh import build_mesh, factor_mesh

    devs = jax.devices()  # global across processes after initialize
    sizes = sizes or factor_mesh(len(devs))
    return build_mesh(len(devs), sizes=sizes, devices=devs), sizes


def process_count() -> int:
    import jax

    return jax.process_count()
