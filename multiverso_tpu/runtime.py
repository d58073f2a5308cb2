"""Process-wide session: the reference's ``Zoo`` re-expressed for TPU.

The reference Zoo (``include/multiverso/zoo.h:19``, ``src/zoo.cpp`` in the
Multiverso reference) is a singleton that starts actor threads, registers the
node with rank 0, owns the table registry, and provides barrier/rank/size
queries. On TPU there are no actor threads to start — the data plane is SPMD
programs over a mesh — so the Session reduces to: flag parsing, topology
discovery, the table registry, the train-mode switches (sync / async / ma),
and lifecycle (init / barrier / shutdown with a dashboard dump,
``src/zoo.cpp:96-101``).
"""

from __future__ import annotations

import os
import threading
from .analysis import lockwatch
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import config, topology
from .dashboard import Dashboard
from .log import Log, LogLevel

_ROLE_NONE, _ROLE_WORKER, _ROLE_SERVER, _ROLE_ALL = 0, 1, 2, 3
_ROLES = {"none": _ROLE_NONE, "worker": _ROLE_WORKER,
          "server": _ROLE_SERVER, "default": _ROLE_ALL, "all": _ROLE_ALL}

# The persistent compile cache lives beside the package unless the
# environment places it: the directory is part of the cache key, so it
# must be the same path in every process and every run of one checkout.
_JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def _place_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on before the first
    compile and return its directory. ``JAX_COMPILATION_CACHE_DIR``,
    where set, is JAX's own to read and nothing is set in code."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", _JAX_CACHE_DIR)
    return _JAX_CACHE_DIR


class Session:
    """Singleton runtime state (``Zoo::Get()`` analogue)."""

    _instance: Optional["Session"] = None
    _lock = lockwatch.rlock("runtime.Session._lock")

    def __init__(self) -> None:
        self.topo: Optional[topology.Topology] = None
        self.tables: List[Any] = []
        self.servers: List[Any] = []  # serving.InferenceServer registry
        self.role: int = _ROLE_ALL
        self.started = False
        self.async_bus: Optional[Any] = None  # cross-process async PS plane
        self.wal: Optional[Any] = None  # -wal write-ahead delta journal
        self.failure_detector: Optional[Any] = None  # -failure_timeout_s
        self.metrics_exporter: Optional[Any] = None  # -metrics_jsonl
        self.obs_agent: Optional[Any] = None  # -obs_plane fleet agent
        self.compile_cache_dir: Optional[str] = None  # set by start()
        # stop() handshake: the claiming caller's completion event +
        # thread id, so a concurrent stop() can wait for the teardown
        # to finish without wedging the Session lock behind it
        self._teardown_done: Optional[threading.Event] = None
        self._teardown_thread: Optional[int] = None

    # -- singleton --------------------------------------------------------
    @classmethod
    def get(cls) -> "Session":
        with cls._lock:
            if cls._instance is None:
                cls._instance = Session()
            return cls._instance

    # -- lifecycle --------------------------------------------------------
    def start(self, argv: Optional[Sequence[str]] = None) -> List[str]:
        """``MV_Init`` (``src/multiverso.cpp:10`` → ``Zoo::Start``).

        A previous stop()'s teardown may still be draining OUTSIDE the
        Session lock (see :meth:`stop`); initializing over it would
        race the old teardown's barriers and distributed shutdown
        against the new session's coordination service — wait for its
        completion event first (same-thread re-entry skips the wait:
        it would deadlock on our own event).
        """
        while True:
            with self._lock:
                done = self._teardown_done
                if (done is None or done.is_set()
                        or self._teardown_thread == threading.get_ident()):
                    return self._start_locked(argv)
            done.wait()

    def _start_locked(self, argv: Optional[Sequence[str]]) -> List[str]:
        with self._lock:
            rest = config.parse_cmd_flags(list(argv) if argv else None)
            Log.reset_log_level_by_name(config.get_flag("log_level"))
            log_file = config.get_flag("log_file")
            if log_file:
                Log.reset_log_file(log_file)
            if self.started:
                return rest
            self.role = _ROLES.get(config.get_flag("ps_role"), _ROLE_ALL)
            self.compile_cache_dir = _place_compile_cache()
            self.topo = topology.discover()
            if self.topo.num_workers % self.topo.size != 0:
                Log.fatal(
                    f"mesh worker axis ({self.topo.num_workers}) must be a "
                    f"multiple of the process count ({self.topo.size}) so "
                    f"every process owns the same number of worker lanes; "
                    f"pass -mesh_shape to fix the layout")
            self.started = True
            if config.get_flag("lockwatch"):
                lockwatch.enable()
            if config.get_flag("trace"):
                from . import trace

                # enable() resets the span ring; the not-enabled() guard
                # keeps a redundant init from wiping a live collector
                if not trace.enabled():
                    tail = None
                    if config.get_flag("trace_tail"):
                        tail = trace.TailConfig(
                            slo_ms=float(config.get_flag("trace_slo_ms")),
                            head_n=int(config.get_flag("trace_head_n")))
                    trace.enable(int(config.get_flag("trace_buffer")),
                                 tail=tail)
            metrics_path = config.get_flag("metrics_jsonl")
            if metrics_path and self.metrics_exporter is None:
                # started only once init validation passed: a failed
                # init must not leak a reporter thread, and a retried
                # init must not double-write the JSONL sink
                from .dashboard import MetricsExporter

                self.metrics_exporter = MetricsExporter(
                    interval_s=float(config.get_flag("metrics_interval_s")),
                    sink=metrics_path).start()
            if config.get_flag("wal") and self.wal is None:
                wal_dir = config.get_flag("wal_dir")
                if not wal_dir:
                    Log.fatal("-wal=true requires -wal_dir=PATH (the "
                              "journal must land somewhere durable)")
                from .io.wal import DeltaWAL

                # construction runs torn-tail recovery and opens a
                # fresh segment for this incarnation
                self.wal = DeltaWAL(
                    wal_dir, rank=self.topo.rank,
                    segment_bytes=int(
                        config.get_flag("wal_segment_mb")) << 20,
                    fsync=config.get_flag("wal_fsync"))
            topology.barrier("mv_init")
            from .parallel.async_ps import AsyncDeltaBus

            self.async_bus = AsyncDeltaBus.maybe_start(self)
            timeout = float(config.get_flag("failure_timeout_s"))
            if timeout > 0 and self.size > 1:
                from .parallel.health import FailureDetector

                self.failure_detector = FailureDetector(
                    interval_s=max(min(1.0, timeout / 5), 0.1), session=self)
                # survivor mode when the async bus is up (dead peers leave
                # the ack quorum and training continues); fail-fast default
                # otherwise (sync collectives can't run degraded)
                self.failure_detector.start_watchdog(
                    timeout,
                    self.async_bus.mark_dead
                    if self.async_bus is not None else None)
            if config.get_flag("obs_plane") and self.obs_agent is None:
                # the fleet observability plane: one agent per node
                # (rank 0 doubles as collector); single-process sessions
                # run it in loopback — same reports, no sockets
                from .serving.obs_plane import ObsAgent

                client = None
                if self.size > 1:
                    from jax._src import distributed

                    client = distributed.global_state.client
                sink = config.get_flag("obs_jsonl")
                if sink and self.size > 1:
                    sink = f"{sink}.{self.rank}"
                self.obs_agent = ObsAgent(
                    rank=self.rank, size=self.size, client=client,
                    report_ms=int(config.get_flag("obs_report_ms")),
                    sink=sink)
            Log.info(
                "multiverso-tpu initialised: rank %d/%d, mesh %s, mode %s",
                self.rank, self.size, dict(self.topo.mesh.shape),
                "ma" if config.get_flag("ma")
                else ("sync" if config.get_flag("sync") else "async"),
            )
            return rest

    def stop(self, finalize: bool = True) -> None:
        """``MV_ShutDown`` → ``Zoo::Stop`` (``src/zoo.cpp:96-101``).

        CLAIMS the session state under the lock, then tears it down
        OUTSIDE: the teardown joins server/batcher threads, blocks on
        cross-process barriers, and invokes the dashboard's log callback
        — seconds of work during which a concurrent ``Session.get()`` or
        table registration must not wedge behind the Session lock
        (locklint LK202/LK203; tests/test_runtime.py covers it).

        stop() still MEANS stopped to its caller: a second concurrent
        stop() finds ``started`` already False and blocks on the first
        caller's completion event instead of returning mid-teardown
        (the old held-lock behavior, minus the lock). Same-thread
        re-entry (a drain callback calling stop()) returns immediately
        — waiting on our own event would self-deadlock.
        """
        claimed = False
        with self._lock:
            if not self.started:
                done = self._teardown_done
                wait = (done is not None and not done.is_set()
                        and self._teardown_thread != threading.get_ident())
            else:
                claimed, wait = True, False
                done = self._teardown_done = threading.Event()
                self._teardown_thread = threading.get_ident()
                self.started = False
                topo, self.topo = self.topo, None
                servers, self.servers = self.servers, []
                # the registry stays readable through the teardown: the
                # bus drain applies in-flight remote deltas by table id
                tables = self.tables
                detector, self.failure_detector = self.failure_detector, None
                bus, self.async_bus = self.async_bus, None
                wal, self.wal = self.wal, None
                exporter, self.metrics_exporter = self.metrics_exporter, None
                obs, self.obs_agent = self.obs_agent, None
        if not claimed:
            if wait:
                done.wait()
            return
        try:
            self._teardown(topo, servers, tables, detector, bus, exporter,
                           obs, wal)
        finally:
            with self._lock:
                self.tables = []
            done.set()

    def _teardown(self, topo, servers, tables, detector, bus,
                  exporter, obs=None, wal=None) -> None:
        # the obs agent ships its FINAL report first, while the engines
        # it summarizes are still alive to be read
        if obs is not None:
            try:
                obs.stop(final_report=True)
            except Exception as exc:
                Log.error("obs plane shutdown failed: %s", exc)
        # serving drains next: in-flight replies read tables, so the
        # inference plane must quiesce before any table is torn down
        for srv in servers:
            try:
                srv.stop()
            except Exception as exc:
                Log.error("serving shutdown failed: %s", exc)
        if detector is not None:
            detector.stop()
        live = None
        if bus is not None and bus._survivor_mode:
            # survivor mode: ALWAYS rendezvous via the KV live-set
            # barrier, not just when the LOCAL dead set is non-empty —
            # a survivor whose watchdog hasn't fired yet would
            # otherwise take the all-process device barrier while its
            # peer takes the live-set one, and both would hang.
            # _live_ranks() unions the KV declarations so all
            # survivors agree on the participant list.
            live = bus._live_ranks()
        topology.barrier("mv_shutdown", live)
        survivor = bus is not None and bus._survivor_mode
        if bus is not None:
            # collective: every in-flight delta lands everywhere before
            # any table is torn down (the reference's FinishTrain drain,
            # src/zoo.cpp:96-101)
            dead = set(bus._dead)
            bus.stop()
        if survivor and topo.size > 1:
            # recoverable tasks skip JAX's synchronized shutdown
            # barrier (the coordination service says so explicitly),
            # so an unsynchronized exit lets the coordinator die
            # mid-peer-disconnect (CANCELLED -> fatal error poll).
            # Rendezvous the live set once more, give peers' own
            # disconnects a grace window on rank 0, and disconnect
            # HERE so the atexit teardown finds nothing left to race.
            live = [r for r in range(topo.size) if r not in dead]
            try:
                topology.barrier("mv_exit", live)
            except Exception as exc:
                Log.info("exit rendezvous incomplete (%s); "
                         "proceeding with shutdown", exc)
            import time as _time

            import jax as _jax

            if topo.rank == 0:
                _time.sleep(1.0)
            try:
                _jax.distributed.shutdown()
            except Exception as exc:
                Log.info("distributed shutdown raced a peer exit "
                         "(benign in survivor mode): %s", exc)
        for table in tables:
            flush = getattr(table, "flush", None)
            if flush is not None:
                flush()
        if wal is not None:
            # after the table flushes: no apply path can append anymore
            # (the bus has stopped)
            wal.close()
        if exporter is not None:
            # final report: the shutdown snapshot lands in the JSONL
            # archive even when the session dies mid-interval
            exporter.stop(final_report=True)
        Dashboard.display()

    def barrier(self) -> None:
        """``MV_Barrier``. In async mode with >1 process this also quiesces
        the delta bus, so barrier-separated phases observe each other's Adds
        — the property the reference's binding tests rely on ("barriers
        between phases make the async PS deterministic", SURVEY §4)."""
        self._require_started()
        if self.async_bus is not None:
            self.async_bus.drain("barrier")
            if self.async_bus._dead:
                # survivor mode: drain's live-set barriers were the
                # rendezvous; a device barrier would wait on the dead peer
                return
        topology.barrier()

    # -- registry ---------------------------------------------------------
    def register_table(self, table: Any) -> int:
        """Assign the next table id (``Zoo::RegisterTable``, ``src/zoo.cpp:172``)."""
        with self._lock:
            self._require_started()
            table_id = len(self.tables)
            self.tables.append(table)
            return table_id

    def table(self, table_id: int) -> Any:
        return self.tables[table_id]

    def register_server(self, server: Any) -> None:
        """Track a serving.InferenceServer so shutdown stops it before
        the tables it reads are torn down."""
        with self._lock:
            self._require_started()
            self.servers.append(server)

    # -- queries (``multiverso.h:18-29``) ---------------------------------
    def _require_started(self) -> None:
        if not self.started or self.topo is None:
            Log.fatal("multiverso-tpu session not initialised; call init() first")

    @property
    def mesh(self):
        self._require_started()
        return self.topo.mesh

    @property
    def table_mesh(self):
        """Mesh parameter tables shard over.

        Sync/MA/single-process: the global mesh (one logical array, BSP
        collectives). Multi-process ASYNC: the process-LOCAL mesh — each
        process holds an independent replica it updates without collective
        participation, and the delta bus (``parallel.async_ps``) provides
        eventual cross-process visibility (the reference's async contract).
        """
        self._require_started()
        if self.async_bus is not None:
            return self.topo.local_mesh
        return self.topo.mesh

    @property
    def rank(self) -> int:
        self._require_started()
        return self.topo.rank

    @property
    def size(self) -> int:
        self._require_started()
        return self.topo.size

    @property
    def num_workers(self) -> int:
        """Size of the ONE worker-id space (dense ids 0..num_workers-1).

        Defined as the mesh ``worker`` axis — the same space the data plane
        shards batches over, the per-worker updater state (AdaGrad slots) is
        sized by, and the bindings' ``workers_num`` reports (the reference's
        dense Zoo worker ids, ``src/zoo.cpp:119-138``). In the canonical
        deployment the worker axis equals the process count (one
        data-parallel worker per process); a single process may declare a
        wider axis (``-mesh_shape``) to drive several worker lanes from one
        host, and then owns all of them.
        """
        self._require_started()
        return self.topo.num_workers

    @property
    def local_workers(self) -> int:
        """Worker lanes owned by this process (num_workers / size)."""
        self._require_started()
        return self.topo.num_workers // self.topo.size

    @property
    def num_servers(self) -> int:
        self._require_started()
        return self.topo.num_servers

    @property
    def worker_id(self) -> int:
        """First worker lane owned by this process (host-side Adds act as
        this worker); lanes are contiguous per process."""
        self._require_started()
        if not (self.role & _ROLE_WORKER):
            return -1
        return self.topo.rank * self.local_workers

    @property
    def server_id(self) -> int:
        self._require_started()
        return self.topo.rank if self.role & _ROLE_SERVER else -1

    def is_worker(self) -> bool:
        return bool(self.role & _ROLE_WORKER)

    def is_server(self) -> bool:
        return bool(self.role & _ROLE_SERVER)

    # -- model averaging ---------------------------------------------------
    def aggregate(self, data: np.ndarray) -> np.ndarray:
        """``MV_Aggregate`` (``src/multiverso.cpp:47-50``): in-place sum of a
        host buffer across all processes. Rides DCN through the JAX
        coordination service instead of ``MPI_Allreduce``; the per-device
        collective form lives in ``parallel.collectives``.
        """
        self._require_started()
        if self.size == 1:
            return data
        from jax.experimental import multihost_utils

        gathered = multihost_utils.process_allgather(np.asarray(data))
        summed = np.sum(gathered, axis=0).astype(data.dtype)
        np.copyto(data, summed)
        return data
