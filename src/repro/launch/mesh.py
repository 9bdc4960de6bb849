"""Production meshes — single-host and multi-host.

Kept as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=... before first jax init,
and smoke tests must keep seeing 1 device.

Multi-host promotion (DESIGN.md §7): ``init_distributed()`` wires
``jax.distributed`` from standard env vars, ``make_multihost_mesh()``
builds a ("host", "data", "model") mesh whose leading axis follows
process boundaries, so per-host data sharding in ``data/pipeline.py``
and cross-host collectives in ``core/distributed.py`` can address hosts
by name.  The collective contract for that mesh is asserted ahead of
time by the dryrun HLO gate (``launch/dryrun.py --gate``) on simulated
host-platform devices, so a topology typo fails in CI, not at pod scale.
"""
from __future__ import annotations

import os

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the partitioner propagates
    shardings from the ``with_sharding_constraint`` annotations this repo
    places (``ShardCtx.act``).  jax.make_mesh's own default is Explicit
    axes, under which sharding is part of every type and an op whose
    output sharding is ambiguous (a gather from a sharded table) fails."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def mesh_shape_for(devices: int, tp: int = 0) -> tuple[int, int]:
    """Pure (dp, tp) shape arithmetic for ``make_mesh_for``.

    tp=0 picks the largest power-of-two TP degree <= min(16, devices).
    Raises ValueError when an explicit tp does not divide devices —
    elastic restarts land on arbitrary survivor counts (1, 2, 4, 6, 8,
    12, ...), so this must be a pointed error, not an assert."""
    if tp <= 0:
        tp = 1
        while tp * 2 <= min(16, devices) and devices % (tp * 2) == 0:
            tp *= 2
    dp, rem = divmod(devices, tp)
    if rem or dp < 1:
        raise ValueError(
            f"cannot build a (data={devices}/{tp}, model={tp}) mesh: "
            f"tp={tp} does not divide devices={devices}; pick a tp that "
            f"divides the surviving device count (or tp=0 to auto-select)")
    return dp, tp


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single pod (256 chips) or 2x16x16 two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh_for(devices: int, tp: int = 0) -> Mesh:
    """Elastic helper: best 2-D mesh for whatever devices survive a restart.

    tp=0 picks the largest power-of-two TP degree <= min(16, devices)."""
    dp, tp = mesh_shape_for(devices, tp)
    return _mesh((dp, tp), ("data", "model"))


def make_debug_mesh(dp: int = 2, tp: int = 4) -> Mesh:
    """Small host-device mesh for tests (needs device_count >= dp*tp)."""
    return _mesh((dp, tp), ("data", "model"))


# ---- multi-host ------------------------------------------------------------

def init_distributed(*, coordinator: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> bool:
    """Wire up ``jax.distributed`` when running multi-process.

    Reads the standard env vars (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES
    / JAX_PROCESS_ID) when args are omitted; a no-op (returns False) on
    single-process runs so tests and smoke scripts never pay cluster-init
    latency.  Must run before first jax device use on every host."""
    coordinator = coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS")
    num_processes = num_processes if num_processes is not None else int(
        os.environ.get("JAX_NUM_PROCESSES", "1"))
    if not coordinator or num_processes <= 1:
        return False
    process_id = process_id if process_id is not None else int(
        os.environ.get("JAX_PROCESS_ID", "0"))
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def make_multihost_mesh(tp: int = 0, *, hosts: int = 0) -> Mesh:
    """("host", "data", "model") mesh with hosts on the leading axis.

    ``hosts`` defaults to ``jax.process_count()`` (real multi-process runs);
    pass it explicitly on simulated host-platform device farms (the dryrun
    gate forces N CPU devices in ONE process and slices them into virtual
    hosts).  Devices are laid out host-major so each mesh row's devices are
    local to one host — per-host data sharding then never crosses a host
    for batch placement, only for the named collectives.

    ``tp`` follows ``mesh_shape_for`` on the per-host device count: the
    model axis never spans hosts (vocab-parallel all-gathers stay on fast
    intra-host links; cross-host traffic is reduced psums over
    ("host", "data"))."""
    hosts = hosts or jax.process_count()
    devices = jax.devices()
    if len(devices) % hosts:
        raise ValueError(
            f"cannot split {len(devices)} devices across hosts={hosts}: "
            "device count must be a multiple of the host count")
    per_host = len(devices) // hosts
    dp, tp = mesh_shape_for(per_host, tp)
    import numpy as np
    dev_grid = np.asarray(devices, dtype=object).reshape(hosts, dp, tp)
    return Mesh(dev_grid, ("host", "data", "model"))
