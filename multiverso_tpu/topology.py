"""Device-mesh topology discovery and process-group control plane.

TPU-native replacement for the reference's Zoo/Controller node registration
(``src/zoo.cpp:37-138``, ``src/controller.cpp:38-80`` in the Multiverso
reference). There, every process sends a ``Control_Register`` message to rank
0, which assigns dense worker/server ids and broadcasts the node table over
MPI/ZMQ. Here the same facts — world size, this process's rank, which devices
exist and how they are arranged — come from the JAX runtime: multi-host
process groups via ``jax.distributed`` over DCN, device topology from
``jax.devices()``, and the data plane is an SPMD ``jax.sharding.Mesh``.

The logical mesh has two axes:

* ``worker`` — the data-parallel axis. Gradients/deltas are summed across it
  (the reference's "N workers each Add a delta" contract).
* ``server`` — the table-shard axis. Parameter tables are laid out with
  ``NamedSharding(mesh, P("server"))`` so each shard is HBM-resident on its
  "server" devices (the reference's range-sharding of tables across server
  nodes, ``src/table/array_table.cpp:11-22``).

A third optional axis ``seq`` supports sequence/context parallelism for
long-context workloads (ring attention in ``ops/ring_attention.py``).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import config
from .log import Log

WORKER_AXIS = "worker"
SERVER_AXIS = "server"
SEQ_AXIS = "seq"


@dataclass
class Topology:
    """Immutable snapshot of the process group + device mesh."""

    mesh: "jax.sharding.Mesh"
    process_index: int
    process_count: int
    devices: List["jax.Device"] = field(default_factory=list)
    _local_mesh: Optional["jax.sharding.Mesh"] = None

    @property
    def num_workers(self) -> int:
        return int(self.mesh.shape[WORKER_AXIS])

    @property
    def local_mesh(self) -> "jax.sharding.Mesh":
        """Mesh over THIS process's devices only (worker=1, server=n_local).

        Async-PS tables live here: each process owns an independent replica
        it can update without collective participation (the global mesh
        would make every ``device_put``/jit a group-wide collective, which
        is exactly what async mode must not require). Deltas cross
        processes via ``parallel.async_ps``, not via array sharding.
        """
        if self._local_mesh is None:
            local = [d for d in self.devices
                     if d.process_index == self.process_index]
            self._local_mesh = make_mesh(
                (1, len(local)), devices=local)
        return self._local_mesh

    @property
    def num_servers(self) -> int:
        return int(self.mesh.shape[SERVER_AXIS])

    @property
    def rank(self) -> int:
        return self.process_index

    @property
    def size(self) -> int:
        return self.process_count


def _parse_mesh_shape(text: str) -> Optional[Tuple[int, ...]]:
    text = text.strip()
    if not text:
        return None
    return tuple(int(p) for p in text.split(",") if p.strip())


def make_mesh(
    shape: Optional[Sequence[int]] = None,
    axis_names: Sequence[str] = (WORKER_AXIS, SERVER_AXIS),
    devices: Optional[Sequence] = None,
) -> "jax.sharding.Mesh":
    """Build a logical mesh over the (global) device set.

    ``shape`` defaults to putting every device on the ``server`` axis
    (pure table sharding, one logical worker per process group) — the
    analogue of the reference default role ``ALL`` where each node both
    computes and serves shards (``src/zoo.cpp:23,31``).
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    if shape is None:
        shape = (1,) * (len(axis_names) - 1) + (n,)
    shape = tuple(int(s) for s in shape)
    if len(shape) != len(axis_names):
        raise ValueError(f"mesh shape {shape} does not match axes {tuple(axis_names)}")
    needed = int(np.prod(shape))
    if needed > n:
        raise ValueError(f"mesh shape {shape} needs {needed} devices, have {n}")
    grid = np.asarray(devices[:needed], dtype=object).reshape(shape)
    return Mesh(grid, axis_names=tuple(axis_names))


# Explicit net bootstrap state (net_bind/net_connect), consulted before the
# env vars by _maybe_init_distributed.
_explicit_net: Dict[str, object] = {}


def net_bind(rank: int, endpoint: str) -> None:
    """Declare THIS process's rank and endpoint (``MV_NetBind``,
    ``include/multiverso/multiverso.h:43-62`` — the reference's MPI-free
    ZMQ deployment mode, where a machine file / explicit bind+connect
    replaces mpirun).

    Call before :func:`multiverso_tpu.init`, paired with
    :func:`net_connect`. In this framework the transport is the JAX
    coordination service, so binding reduces to declaring identity; the
    per-rank data endpoints of the reference collapse into the single
    coordinator endpoint (rank 0's).
    """
    _explicit_net["rank"] = int(rank)
    _explicit_net["endpoint"] = str(endpoint)


def net_connect(ranks: Sequence[int], endpoints: Sequence[str]) -> None:
    """Declare the full group (``MV_NetConnect``): ``endpoints[i]`` is rank
    ``ranks[i]``'s endpoint; rank 0's endpoint becomes the coordinator.
    Call before :func:`multiverso_tpu.init` (after :func:`net_bind`)."""
    ranks = [int(r) for r in ranks]
    if len(ranks) != len(endpoints):
        Log.fatal(f"net_connect: {len(ranks)} ranks vs "
                  f"{len(endpoints)} endpoints")
    if len(set(ranks)) != len(ranks):
        Log.fatal(f"net_connect: duplicate ranks in {ranks}")
    table = dict(zip(ranks, endpoints))
    if 0 not in table:
        Log.fatal("net_connect needs rank 0's endpoint (the coordinator)")
    _explicit_net["num"] = len(table)
    _explicit_net["coordinator"] = str(table[0])


def _survivor_mode_prep() -> None:
    """Survivor mode (``-failure_timeout_s > 0``) needs the coordination
    service itself to tolerate a dead task: without
    ``jax_enable_recoverability`` the service's error polling terminates
    every HEALTHY process ~heartbeat_timeout after a peer dies —
    regardless of the framework-level live-set machinery. Fail-fast
    stays the default for non-survivor jobs (the reference's posture: a
    silent peer kills the job)."""
    try:
        from . import config as _config

        survivor = float(_config.get_flag("failure_timeout_s")) > 0
    except Exception as exc:   # flag registry not up yet -> default mode
        Log.debug("survivor-mode prep skipped: %s", exc)
        return
    if not survivor:
        return
    try:
        import jax

        jax.config.update("jax_enable_recoverability", True)
    except Exception as exc:
        # the user EXPLICITLY asked for survivor mode; silently reverting
        # to fail-fast would let a dead peer kill every healthy survivor
        Log.error("survivor mode requested (-failure_timeout_s) but "
                  "jax_enable_recoverability could not be enabled (%s): "
                  "the coordination service will terminate survivors "
                  "~heartbeat_timeout after a peer death", exc)


def _maybe_init_distributed() -> None:
    """Initialise the multi-host process group if asked to.

    Replaces MPI_Init + rank-0 registration: coordination rides DCN via the
    JAX coordination service. Bootstrap sources, in order: the explicit
    net_bind/net_connect API (the reference's machine-file/ZMQ mode), then
    the MV_*/JAX_* coordinator env vars. Single-process runs skip this.

    A caller that asked for a group gets one or a fatal error: carrying
    on as ``rank 0/1`` would train alone and report success.
    """
    _survivor_mode_prep()
    # Read the env BEFORE touching any jax API: probing jax.process_count()
    # would itself initialise the local backend, after which
    # jax.distributed.initialize() raises.
    if "coordinator" in _explicit_net and "rank" in _explicit_net:
        source = "explicit net"
        coord = str(_explicit_net["coordinator"])
        nproc = int(_explicit_net["num"])
        rank = int(_explicit_net["rank"])
    else:
        source = "env"
        coord = os.environ.get("MV_COORDINATOR_ADDRESS") or os.environ.get(
            "JAX_COORDINATOR_ADDRESS")
        nproc = os.environ.get("MV_NUM_PROCESSES")
        if not (coord and nproc):
            return
        nproc = int(nproc)
        rank = int(os.environ.get("MV_PROCESS_ID", "0"))
    import jax

    # a second init() in a process whose group is already up (by the
    # launcher or a prior init()) keeps that group
    if not jax.distributed.is_initialized():
        try:
            jax.distributed.initialize(coordinator_address=coord,
                                       num_processes=nproc, process_id=rank)
        except RuntimeError as exc:
            Log.fatal(f"process group ({source}) requested via {coord} "
                      f"(rank {rank}/{nproc}) but jax.distributed."
                      f"initialize failed: {exc}")
    Log.info("process group (%s): rank %d/%d via %s", source,
             jax.process_index(), jax.process_count(), coord)


def discover(mesh_shape: Optional[Sequence[int]] = None) -> Topology:
    """Discover the topology; the ``mesh_shape`` flag/argument overrides.

    Default layout: ``worker`` axis = number of processes (each host is one
    data-parallel worker, mirroring one-node-one-worker in the reference),
    ``server`` axis = devices per process (tables sharded across local chips).
    """
    import jax

    _maybe_init_distributed()
    if mesh_shape is None:
        mesh_shape = _parse_mesh_shape(config.get_flag("mesh_shape"))

    devices = jax.devices()
    n = len(devices)
    if mesh_shape is None:
        workers = jax.process_count()
        if n % workers != 0:
            workers = 1
        mesh_shape = (workers, n // workers)

    mesh = make_mesh(mesh_shape, devices=devices)
    topo = Topology(
        mesh=mesh,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        devices=devices,
    )
    Log.debug(
        "topology: %d device(s), mesh %s, process %d/%d",
        n, dict(mesh.shape), topo.process_index, topo.process_count,
    )
    return topo


def barrier(name: str = "mv_barrier", participants=None) -> None:
    """Global process barrier.

    Replaces the reference's rank-0 BarrierController round-trip
    (``src/controller.cpp:16-31``): the JAX coordination service provides the
    same rendezvous over DCN; a single-process group is a no-op.

    ``participants`` (survivor mode): rendezvous only the given live
    process ids via a coordination-service barrier — a device-collective
    barrier over ALL processes would wait on the dead peer forever. Pass
    it only from one-shot phases (e.g. shutdown): KV barrier ids are
    single-use per name.
    """
    import jax

    if jax.process_count() > 1:
        if participants is not None:
            from jax._src import distributed

            client = distributed.global_state.client
            if client is not None:
                client.wait_at_barrier(f"mvb/{name}", 600_000,
                                       sorted(participants))
                return
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def sharding_for(mesh, *axes: Optional[str]):
    """NamedSharding helper: ``sharding_for(mesh, SERVER_AXIS)`` etc."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(*axes))
