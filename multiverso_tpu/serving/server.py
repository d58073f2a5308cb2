"""Request router: named models -> micro-batchers -> jitted workloads.

The front door of the serving subsystem. Each registered model owns a
:class:`MicroBatcher` (bounded queue, size/deadline flush, shape
buckets, load-shedding) and a :class:`SnapshotManager` (versioned
copy-on-publish read view). A flush takes ONE snapshot decision for the
whole batch, executes the workload's jitted program against it, and
stamps every reply with the snapshot version and its staleness bound —
so a client can always tell how far behind live training its answer is.

Lifecycle ties into the Session: a started server registers itself, and
``Session.stop()`` (``mv.shutdown()``) stops serving before tables are
torn down — the reference Zoo's shutdown-order contract extended to the
inference plane.

A fleet deployment scales this out behind :class:`~.router.FleetRouter`
(``mvserve``), optionally with role-specialized replicas — prefill
ranks chunk-prefill prompts and ship the finished paged-KV blocks over
the wire to decode ranks (:mod:`.kv_transfer`, docs/SERVING.md
"Disaggregated prefill/decode"); this in-process server is the
``unified`` role both specializations degrade to.
"""

from __future__ import annotations

import threading
from ..analysis import lockwatch
from concurrent.futures import Future
from typing import Any, Dict, List, Optional

from .. import trace
from ..log import Log
from .batcher import BatcherConfig, MicroBatcher
from .decode_engine import DecodeEngine, DecodeEngineConfig
from .snapshot import SnapshotManager


class _DecoderEntry:
    """A continuous-batching LM: requests route to a :class:`DecodeEngine`
    (iteration-level scheduling) instead of a :class:`MicroBatcher`."""

    def __init__(self, name: str, engine: DecodeEngine) -> None:
        self.name = name
        self.engine = engine

    def submit(self, payload: Any,
               ctx: Optional[trace.SpanContext] = None) -> Future:
        """Payload: a 1-D prompt id array, or a dict with ``prompt`` and
        optional per-request ``max_new``, ``priority`` (tenant class,
        0..7, higher = more important), ``deadline_s`` (seconds from
        now past which the reply is worthless — expired requests drop
        at queue-pop time with ``DeadlineExceededError``, before any
        prefill runs) and ``tenant`` (accounting id for the cost
        ledger; absent = the ``-default_tenant`` flag)."""
        if isinstance(payload, dict):
            if "prompt" not in payload:
                raise ValueError("decoder payload dict needs a 'prompt' key")
            return self.engine.submit(payload["prompt"],
                                      payload.get("max_new"), ctx=ctx,
                                      priority=payload.get("priority"),
                                      deadline_s=payload.get("deadline_s"),
                                      tenant=payload.get("tenant"))
        return self.engine.submit(payload, ctx=ctx)


class _ModelEntry:
    def __init__(self, name: str, workload, manager: SnapshotManager,
                 batcher_cfg: BatcherConfig, max_staleness_s: float) -> None:
        self.name = name
        self.workload = workload
        self.manager = manager
        self.max_staleness_s = float(max_staleness_s)
        self.batcher = MicroBatcher(name, self._run, batcher_cfg)

    def _run(self, payloads: List[Any], bucket: int) -> List[dict]:
        # ONE freshness decision per flush: every reply in the batch is
        # built from the same snapshot, and its staleness at flush time
        # is bounded by max_staleness_s (ensure_fresh republishes past it)
        snap = self.manager.ensure_fresh(self.max_staleness_s)
        staleness = self.manager.staleness_s(snap)
        results = self.workload.run(payloads, bucket, snap)
        return [{"result": r, "snapshot_version": snap.version,
                 "staleness_s": staleness} for r in results]


class InferenceServer:
    """Batched low-latency inference over live parameter state."""

    def __init__(self, name: str = "serving") -> None:
        self.name = name
        self._models: Dict[str, _ModelEntry] = {}
        self._lock = lockwatch.lock("serving.InferenceServer._lock")
        self._stopped = False
        from ..runtime import Session

        sess = Session.get()
        if sess.started:
            sess.register_server(self)

    # -- registration -------------------------------------------------------
    def register(self, name: str, workload, max_batch: int = 32,
                 deadline_ms: float = 2.0, max_queue: int = 256,
                 max_staleness_s: float = 0.05,
                 buckets: Optional[tuple] = None) -> None:
        """Attach a workload under ``name``.

        ``workload`` exposes ``source`` (a table or model with the
        snapshot contract) and ``run(payloads, bucket, snap)``; knobs:
        ``max_batch``/``deadline_ms`` set the flush triggers,
        ``max_queue`` the shed threshold, ``max_staleness_s`` the
        snapshot refresh bound.
        """
        cfg = BatcherConfig(max_batch=max_batch, deadline_ms=deadline_ms,
                            max_queue=max_queue, buckets=buckets)
        manager = SnapshotManager.of(workload.source, name=name)
        with self._lock:
            if self._stopped:
                Log.fatal(f"serving: register({name!r}) on a stopped "
                          f"server")
            if name in self._models:
                Log.fatal(f"serving: model {name!r} already registered")
            self._models[name] = _ModelEntry(
                name, workload, manager, cfg, max_staleness_s)
        Log.info("serving: model %r up (max_batch %d, deadline %.1f ms, "
                 "queue cap %d)", name, max_batch, deadline_ms, max_queue)

    def register_decoder(self, name: str, lm, *, slots: int = 8,
                         max_prompt: int = 64, max_new: int = 32,
                         eos_id: Optional[int] = None, max_queue: int = 256,
                         max_staleness_s: float = 0.05,
                         prefill_token_budget: Optional[int] = None,
                         kv_block_size: Optional[int] = None,
                         kv_pool_blocks: Optional[int] = None,
                         decode_tp: Optional[int] = None,
                         prefix_cache: Optional[bool] = None,
                         prefill_sp: Optional[bool] = None,
                         prefill_sp_backend: Optional[str] = None,
                         prefill_sp_threshold: Optional[int] = None,
                         spec_k: Optional[int] = None,
                         kv_quant: Optional[str] = None,
                         decode_param_quant: Optional[str] = None,
                         preempt: Optional[bool] = None,
                         preempt_budget: Optional[int] = None,
                         sched_lookahead: Optional[int] = None,
                         watchdog: Optional[bool] = None,
                         debug_dump_dir: Optional[str] = None,
                         slo_ttft_ms: Optional[float] = None,
                         slo_itl_ms: Optional[float] = None,
                         cost_ledger: Optional[bool] = None
                         ) -> DecodeEngine:
        """Attach a continuous-batching decode engine under ``name``.

        Unlike :meth:`register`'s micro-batched ``LMGreedyDecode``,
        ``submit`` routes straight into the engine: admission, decode,
        and completion all happen at iteration granularity (no request
        ever waits for a co-batched stranger's generation to finish).
        Payloads are 1-D prompt id arrays, or ``{"prompt": ...,
        "max_new": n}`` for a per-request generation cap.
        ``prefill_token_budget`` bounds the prefill work any single
        iteration interleaves with decode (chunked admission; None =
        the ``-prefill_token_budget`` flag; must be > 0).
        ``kv_block_size``/``kv_pool_blocks`` size the paged KV cache
        (None = the ``-kv_block_size``/``-kv_pool_blocks`` flags;
        the block size must be > 0): pool
        capacity rather than slot geometry bounds concurrency, and a
        submit whose ``prompt + max_new`` can never fit the pool sheds
        with :class:`OverloadedError` (docs/SERVING.md "Paged KV
        cache"). ``decode_tp`` (None = the ``-decode_tp`` flag, default
        1) sets the tensor-parallel width of the decode mesh: heads/MLP
        shards + head-sharded K/V pools over the first ``decode_tp``
        devices, params resharded once per snapshot pin, per-token
        programs compiled once against matched shardings — the knob
        that serves models bigger than one device (docs/SERVING.md
        "Sharded decode"; 1 = the replicated single-device path).
        ``prefix_cache`` (None = the ``-prefix_cache`` flag,
        default on) turns on content-addressed block reuse over that
        pool: prompts sharing a prefix prefill it once and splice the
        cached blocks refcounted/copy-on-write (docs/SERVING.md
        "Prefix caching"). ``prefill_sp`` (None = the ``-prefill_sp``
        flag, default off; sharded or single-device)
        turns on sequence-parallel long-prompt prefill: prompts of at
        least ``prefill_sp_threshold`` tokens prefill in
        ``prefill_token_budget * decode_tp`` token chunks whose rows
        shard over the decode mesh via ``prefill_sp_backend`` ("ring"
        ppermute rotations or "ulysses" all_to_all head resharding) —
        a long document admits in ``decode_tp`` x fewer iterations
        while each device still runs one budget of rows per iteration,
        and shorter prompts keep the single-lane chunk program
        bit-for-bit (docs/SERVING.md "Long-context prefill").
        ``spec_k`` (None = the ``-spec_k`` flag,
        default 0 = off) turns on speculative decoding: up to
        ``spec_k`` n-gram prompt-lookup drafts per live slot, verified
        by one fused fixed-K step per iteration — up to ``spec_k + 1``
        tokens per iteration, outputs token-identical to plain greedy
        decode (docs/SERVING.md "Speculative decoding"). ``kv_quant`` (None = the ``-kv_quant`` flag,
        default "none") stores the paged K/V pools as int8 with
        per-(layer, block) fp32 scales — ~4x the KV capacity at equal
        pool bytes, lossy (the bench archives the argmax-match rate);
        "none" keeps today's fp pools bit-for-bit.
        ``decode_param_quant`` (None = the ``-decode_param_quant``
        flag, default "none") pins int8-quantized decode param
        snapshots and folds the dequant into the compiled programs —
        ~4x smaller pin copies (docs/SERVING.md "Quantized KV &
        params"). ``preempt`` (None = the ``-preempt`` flag,
        default on) switches admission to
        OPTIMISTIC prompt-only reservation with grow-at-decode and
        preemption-with-recompute under pool pressure —
        ``preempt_budget`` bounds how often one request may be
        preempted and ``sched_lookahead`` bounds admission lookahead
        past a block-starved queue head (docs/SERVING.md "Overload
        and preemption"; ``preempt=False`` restores the worst-case
        ``prompt + max_new`` up-front reservation).

        The black-box layer rides along by default: an always-on
        flight recorder (``engine.recorder``) and a stall/leak/queue-age
        watchdog (``watchdog``/``debug_dump_dir`` override the
        ``-watchdog``/``-debug_dump_dir`` flags); ``slo_ttft_ms``/
        ``slo_itl_ms`` register rolling-window p99 SLOs whose burn
        status rides every ``Dashboard.snapshot()``
        (docs/OBSERVABILITY.md "Flight recorder" / "Watchdog").
        ``cost_ledger`` (None = the ``-cost_ledger`` flag, default
        off) attaches a host-only per-tenant :class:`CostLedger`:
        every request accumulates a resource vector (queue wait,
        prefill/decode tokens, KV block-seconds, device step ms,
        transfer bytes, recompute) attributed to its ``tenant``
        payload key and folded into bounded-cardinality per-tenant
        aggregates and cost units at completion
        (docs/OBSERVABILITY.md "Tenant accounting").
        """
        cfg = DecodeEngineConfig(
            slots=slots, max_prompt=max_prompt, max_new=max_new,
            eos_id=eos_id, max_queue=max_queue,
            max_staleness_s=max_staleness_s,
            prefill_token_budget=prefill_token_budget,
            kv_block_size=kv_block_size, kv_pool_blocks=kv_pool_blocks,
            decode_tp=decode_tp, prefix_cache=prefix_cache,
            prefill_sp=prefill_sp,
            prefill_sp_backend=prefill_sp_backend,
            prefill_sp_threshold=prefill_sp_threshold,
            spec_k=spec_k, kv_quant=kv_quant,
            decode_param_quant=decode_param_quant,
            preempt=preempt, preempt_budget=preempt_budget,
            sched_lookahead=sched_lookahead,
            watchdog=watchdog, debug_dump_dir=debug_dump_dir,
            slo_ttft_ms=slo_ttft_ms, slo_itl_ms=slo_itl_ms,
            cost_ledger=cost_ledger)
        with self._lock:
            if self._stopped:
                Log.fatal(f"serving: register_decoder({name!r}) on a "
                          f"stopped server")
            if name in self._models:
                Log.fatal(f"serving: model {name!r} already registered")
        # engine construction dispatches the params replica copy and the
        # warmup compiles — seconds of work that must happen OUTSIDE the
        # registry lock, or every submit() to every OTHER model wedges
        # behind it (locklint LK203; tests/test_serving.py covers it)
        entry = _DecoderEntry(name, DecodeEngine(name, lm, cfg))
        with self._lock:
            # re-check BOTH races lost during construction: a duplicate
            # registration, and a stop() whose entries snapshot predates
            # this entry (the engine's loop thread would outlive the
            # server, reading tables Session teardown is flushing)
            raced = name in self._models
            stopped = self._stopped
            if not raced and not stopped:
                self._models[name] = entry
        if raced or stopped:
            entry.engine.stop()           # join happens OUTSIDE the lock
            Log.fatal(f"serving: model {name!r} already registered" if raced
                      else f"serving: server stopped during decoder "
                           f"{name!r} registration")
        Log.info("serving: decoder %r up (%d slots, max_prompt %d, "
                 "max_new %d)", name, slots, max_prompt, max_new)
        return entry.engine

    def _entry(self, name: str) -> _ModelEntry:
        with self._lock:
            entry = self._models.get(name)
        if entry is None:
            Log.fatal(f"serving: unknown model {name!r} "
                      f"(registered: {sorted(self._models)})")
        return entry

    # -- request path -------------------------------------------------------
    def submit(self, model: str, payload: Any) -> Future:
        """Enqueue one request; raises :class:`OverloadedError` at the
        queue-depth cap and ``ValueError`` for a malformed payload (the
        workload's submit-time ``validate`` — a bad request must reject
        HERE, not poison every co-batched request at flush). The future
        resolves to a reply dict:
        ``{"result", "snapshot_version", "staleness_s"}``.

        When tracing is on (``trace.enable()`` / ``-trace``), each
        request gets a ROOT span ``serve.request`` covering
        submit -> reply; its handoff token rides the queue entry so the
        batcher/engine threads attach queue-wait, admission and decode
        child spans to the same trace id (docs/OBSERVABILITY.md)."""
        entry = self._entry(model)
        root = trace.start_span("serve.request", root=True, model=model)
        try:
            if isinstance(entry, _DecoderEntry):
                fut = entry.submit(payload, ctx=root.context)
            else:
                validate = getattr(entry.workload, "validate", None)
                if validate is not None:
                    validate(payload)
                fut = entry.batcher.submit(payload, ctx=root.context)
        except Exception as exc:
            # shed / validation reject: the root span still closes, so
            # rejected requests are visible in the trace with the reason
            root.end(error=type(exc).__name__)
            raise
        if root is not trace.NULL_SPAN:
            fut.add_done_callback(lambda f, sp=root: sp.end(
                ok=(not f.cancelled()) and f.exception() is None))
        return fut

    def predict(self, model: str, payload: Any,
                timeout_s: float = 30.0) -> dict:
        """Blocking request -> reply dict."""
        return self.submit(model, payload).result(timeout=timeout_s)

    # -- introspection ------------------------------------------------------
    def stats(self, model: str) -> dict:
        entry = self._entry(model)
        if isinstance(entry, _DecoderEntry):
            return entry.engine.stats()
        return {**entry.batcher.stats(),
                "snapshot_publishes": entry.manager.publishes,
                "queue_depth": entry.batcher.queue_depth()}

    def models(self) -> List[str]:
        with self._lock:
            return sorted(self._models)

    # -- lifecycle ----------------------------------------------------------
    def stop(self) -> None:
        with self._lock:
            if self._stopped:
                return
            self._stopped = True
            entries = list(self._models.values())
        for entry in entries:
            if isinstance(entry, _DecoderEntry):
                entry.engine.stop()
            else:
                entry.batcher.stop()
