"""Always-on flight recorder: a bounded ring of per-iteration engine records.

Tracing (``trace.py``) explains one request, and only when it was ON.
The flight recorder is the black box that is ALWAYS running: every
decode-engine iteration appends one small record — what the engine was
doing, how long the fused step took, who was admitted/completed, how
deep and how old the queue was, what the block pool held — into a
preallocated ring. When something wedges, leaks, or a replica dies, the
last ``capacity`` iterations of evidence are already in memory: the
watchdog dumps them, the bench archives their summary, and
``tools/engine_timeline.py`` renders utilization/bubble analysis from a
dump after the fact.

Cost posture: ONE tuple + one short-lock ring append per iteration (the
iteration itself allocates numpy arrays and syncs the device — the
record is noise next to that), and strictly host-side state, so it can
never add a compiled trace. Nothing is serialized until someone asks
(``export_jsonl`` / ``chrome_counter_events``).

Record schema (:data:`FIELDS`, positional):

======================  =====================================================
``it``                  iteration index (1-based, monotonic per engine)
``ts``                  ``time.monotonic()`` at record time (iteration end)
``busy_ms``             wall of this loop pass's work (admit + chunk + step)
``step_ms``             the fused decode steps' share of ``busy_ms``: the
                        launch of the step this pass dispatched, the
                        wait for the tokens of the one it retired (the
                        pass before's, the loop runs one pass ahead)
                        and their booking (0 if it did neither)
``live``                live slots after the pass
``reserved``            mid-prefill admissions (reserved-not-live slots)
``queue``               admission-queue depth after the pass
``queue_age_ms``        age of the OLDEST queued request (0 if empty)
``prefill_toks``        prompt tokens of the chunk dispatched THIS pass
``decode_toks``         tokens booked THIS pass (first tokens included)
``pool_free``           KV pool free blocks
``pool_live``           KV pool live blocks
``pool_shared``         prefix-cache shared blocks — live blocks held by
                        >= 2 sequences
``version``             pinned snapshot version (-1 before the first pin)
``admitted``            request ids admitted this pass (tuple, usually empty)
``completed``           request ids completed this pass (tuple)
``spec_proposed``       speculative drafts verified this pass (-1 when
                        ``spec_k=0`` — the engine isn't speculating)
``spec_accepted``       speculative drafts ACCEPTED this pass (-1 when
                        ``spec_k=0``); accepted/proposed per time bucket
                        is the acceptance-rate strip
                        ``tools/engine_timeline.py`` renders
``kv_quant``            1 when the KV pools are int8-quantized, 0 for
                        fp pools
``quant_scale_blocks``  pool blocks carrying a nonzero quant scale (a
                        written-block occupancy proxy; -1 when
                        ``kv_quant`` != 1)
``kv_block_s``          KV block-seconds charged to tenant usage vectors
                        THIS pass (the cost ledger's residency integral;
                        -1 when ``-cost_ledger`` is off)
``tenants_live``        live tenant cardinality in the cost ledger's
                        aggregate table (-1 when ``-cost_ledger`` is off)
``sp_chunks``           prefill chunks dispatched through the sequence-
                        parallel program THIS pass (-1 when
                        ``-prefill_sp`` is off)
``kv_live_block_share`` KV blocks this pass's step had to read (live
                        slots' ``ceil((pos + 1) / Bs)``) over ``slots x
                        M`` (-1 when the pass ran no step)
``chunks_behind_step``  1 when this pass's prefill chunk was dispatched
                        while its step was in flight (queued behind it
                        on the device), else 0
``steps_ahead``         1 when this pass's step was dispatched with the
                        step before still unread (its tokens taken from
                        the device), else 0
``phases``              the pass's row of the engine's phase clock: the
                        ms under each leaf phase of the pass, in the
                        order the meta line's ``phases`` names them
                        (``engine.record``'s entry ends where the record
                        is built); ``busy_ms`` less their sum is the
                        pass's time under no phase
``gap_ms``              the loop gap before this pass: the loop thread's
                        ms between the end of the pass before and the
                        start of this one that no ``engine.wait`` covers
======================  =====================================================

Timestamps are monotonic; the recorder captures a wall/mono anchor at
construction so exports rebase to epoch microseconds — the same
timebase the span export uses, which is what lets
``chrome_counter_events`` merge into a ``trace.export_chrome`` document
as counter tracks under the request spans (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import json
import os
import threading
import time

try:
    from ..analysis import lockwatch
except ImportError:
    # Loaded standalone by file path (tools/engine_timeline.py keeps its
    # digest math jax-free by exec'ing this module outside the package).
    # A second lockwatch copy would fork the witness registry, so fall
    # back to plain locks — the witness only matters in-package.
    class _PlainLocks:
        @staticmethod
        def lock(name):
            return threading.Lock()

    lockwatch = _PlainLocks()  # type: ignore[assignment]
from typing import Any, Dict, List, Optional

# new columns append at the END: readers index the stable prefix
# positionally, and a pre-PR-11 dump (15/16-field records) still zips
# cleanly against the longer FIELDS — consumers read the tail columns
# with .get() defaults (the PR 8 pool_shared pattern)
FIELDS = ("it", "ts", "busy_ms", "step_ms", "live", "reserved", "queue",
          "queue_age_ms", "prefill_toks", "decode_toks", "pool_free",
          "pool_live", "pool_shared", "version", "admitted", "completed",
          "spec_proposed", "spec_accepted", "kv_quant",
          "quant_scale_blocks", "kv_block_s", "tenants_live", "sp_chunks",
          "kv_live_block_share", "chunks_behind_step", "steps_ahead",
          "phases", "gap_ms")


def window_digest(records: List[Dict[str, Any]],
                  phases: Optional[List[str]] = None) -> Dict[str, Any]:
    """Whole-window utilization digest over dict records (oldest first) —
    the ONE copy of the wall/busy/gap math shared by
    :meth:`FlightRecorder.summary` and ``tools/engine_timeline.py``
    (which loads this stdlib-only module by file path to stay jax-free).

    The window opens when the first retained iteration's work began
    (``ts - busy_ms``) and closes at the last record. ``gaps`` lists
    every idle bubble — time between consecutive records net of the
    later iteration's own work — sorted largest first.

    ``phases`` (the dump's meta line's) names the ``phases`` column:
    ``phase_ms`` then sums it by phase over the records that carry it,
    with the passes' time under no phase (``unphased``) and the loop
    gaps between them (``loop_gap``), and ``slowest`` lists the three
    longest passes with their rows. Records from before the column
    leave both empty."""
    if not records:
        return {"wall_s": 0.0, "busy_frac": 0.0, "idle_frac": 0.0,
                "prefill_tokens": 0, "decode_tokens": 0,
                "prefill_share": 0.0, "steps": 0, "mean_step_ms": 0.0,
                "max_idle_gap_ms": 0.0, "peak_live": 0, "gaps": [],
                "phase_ms": {}, "slowest": []}
    t0 = records[0]["ts"] - records[0]["busy_ms"] / 1e3
    wall = max(records[-1]["ts"] - t0, 1e-9)
    busy_s = sum(r["busy_ms"] for r in records) / 1e3
    steps = [r["step_ms"] for r in records if r["step_ms"] > 0.0]
    prefill = sum(r["prefill_toks"] for r in records)
    decode = sum(r["decode_toks"] for r in records)
    gaps = []
    for i in range(1, len(records)):
        gap = ((records[i]["ts"] - records[i - 1]["ts"]) * 1e3
               - records[i]["busy_ms"])
        if gap > 0.0:
            gaps.append({"t_s": round(records[i]["ts"] - t0, 6),
                         "gap_ms": round(gap, 3),
                         "it": records[i]["it"]})
    gaps.sort(key=lambda g: g["gap_ms"], reverse=True)
    phase_ms: Dict[str, float] = {}
    slowest: List[Dict[str, Any]] = []
    rowed = [r for r in records if r.get("phases")] if phases else []
    if rowed:
        for i, name in enumerate(phases):
            phase_ms[name] = sum(r["phases"][i] for r in rowed)
        phase_ms["unphased"] = (sum(r["busy_ms"] for r in rowed)
                                - sum(phase_ms.values()))
        phase_ms["loop_gap"] = sum(r.get("gap_ms", 0.0) for r in rowed)
        slowest = [{"it": r["it"], "t_s": round(r["ts"] - t0, 6),
                    "busy_ms": r["busy_ms"],
                    "gap_ms": r.get("gap_ms", 0.0),
                    "phases": dict(zip(phases, r["phases"]))}
                   for r in sorted(rowed, key=lambda r: r["busy_ms"],
                                   reverse=True)[:3]]
    return {
        "wall_s": wall,
        "busy_frac": min(1.0, busy_s / wall),
        "idle_frac": max(0.0, 1.0 - busy_s / wall),
        "prefill_tokens": prefill,
        "decode_tokens": decode,
        "prefill_share": (prefill / (prefill + decode)
                          if prefill + decode else 0.0),
        "steps": len(steps),
        "mean_step_ms": sum(steps) / len(steps) if steps else 0.0,
        "max_idle_gap_ms": gaps[0]["gap_ms"] if gaps else 0.0,
        "peak_live": max(r["live"] + r["reserved"] for r in records),
        "gaps": gaps,
        "phase_ms": phase_ms,
        "slowest": slowest,
    }


class FlightRecorder:
    """Bounded ring of per-iteration records (oldest overwritten)."""

    def __init__(self, capacity: int = 4096, name: str = "") -> None:
        if capacity < 1:
            raise ValueError(f"FlightRecorder capacity must be >= 1, "
                             f"got {capacity}")
        self.name = name
        self.capacity = int(capacity)
        # static engine facts (decode_tp, mesh_devices, ...) the owner
        # attaches once; ride every summary() and the JSONL meta line so
        # a post-mortem dump identifies its mesh config
        self.meta: Dict[str, Any] = {}
        self._buf: List[Optional[tuple]] = [None] * self.capacity
        self._pos = 0
        self._n = 0
        self.total = 0                     # records ever written
        self._lock = lockwatch.lock("serving.FlightRecorder._lock")
        # monotonic->epoch anchor (export timebase, merges with spans)
        self._anchor_wall = time.time()
        self._anchor_mono = time.monotonic()

    # -- write (the engine loop) --------------------------------------------
    def record(self, rec: tuple) -> None:
        """Append one record (a tuple in :data:`FIELDS` order)."""
        with self._lock:
            self._buf[self._pos] = rec
            self._pos = (self._pos + 1) % self.capacity
            self._n = min(self._n + 1, self.capacity)
            self.total += 1

    # -- read ---------------------------------------------------------------
    def _tuples(self) -> List[tuple]:
        with self._lock:
            if self._n < self.capacity:
                out = self._buf[: self._n]
            else:
                out = self._buf[self._pos:] + self._buf[: self._pos]
        return [r for r in out if r is not None]

    def records(self) -> List[Dict[str, Any]]:
        """Retained records as dicts, oldest first."""
        return [dict(zip(FIELDS, r)) for r in self._tuples()]

    def to_epoch_us(self, t_mono: float) -> float:
        return (self._anchor_wall + (t_mono - self._anchor_mono)) * 1e6

    def summary(self) -> Dict[str, Any]:
        """Whole-ring utilization digest (the bench's ``_info`` archive
        and the watchdog bundle's headline numbers).

        ``idle_frac`` is 1 - busy/wall over the retained window; the
        biggest single idle gap rides along because a mean hides exactly
        the bubble an operator is hunting."""
        recs = self.records()
        out: Dict[str, Any] = {
            "name": self.name, "iterations": self.total,
            "retained": len(recs), "capacity": self.capacity,
            "wrapped": self.total > self.capacity,
            **self.meta,
        }
        digest = window_digest(recs)
        # the per-bubble and per-phase lists are timeline_report's
        # concern; the digest here rides in bench JSON lines, so keep it
        # scalar-only
        for key in ("gaps", "peak_live", "phase_ms", "slowest"):
            digest.pop(key)
        out.update(digest)
        return out

    # -- export -------------------------------------------------------------
    def export_jsonl(self, path: str) -> int:
        """One meta line, then one JSON line per retained record (oldest
        first) — the dump format ``tools/engine_timeline.py`` consumes.
        Returns the record count written."""
        recs = self.records()
        with open(path, "w") as f:
            f.write(json.dumps({"flight_recorder": {
                "name": self.name, "capacity": self.capacity,
                "total": self.total, "retained": len(recs),
                "anchor_epoch_s": self._anchor_wall,
                "anchor_mono_s": self._anchor_mono,
                "fields": list(FIELDS),
                **self.meta,
            }}) + "\n")
            for rec in recs:
                f.write(json.dumps(rec) + "\n")
        return len(recs)

    def chrome_counter_events(self) -> List[dict]:
        """Chrome ``ph: "C"`` counter samples, one track family per
        engine, on the span export's epoch-µs timebase — load the merged
        document in Perfetto and the engine's occupancy/queue/token
        counters render directly under the request spans."""
        pid = os.getpid()
        events: List[dict] = []
        prefix = f"fr/{self.name or 'engine'}"
        for r in self._tuples():
            ts = self.to_epoch_us(r[1])
            events.append({"name": f"{prefix}/slots", "ph": "C", "ts": ts,
                           "pid": pid, "tid": 0,
                           "args": {"live": r[4], "reserved": r[5]}})
            events.append({"name": f"{prefix}/queue", "ph": "C", "ts": ts,
                           "pid": pid, "tid": 0,
                           "args": {"depth": r[6]}})
            events.append({"name": f"{prefix}/tokens", "ph": "C", "ts": ts,
                           "pid": pid, "tid": 0,
                           "args": {"prefill": r[8], "decode": r[9]}})
            if r[10] >= 0:
                events.append({"name": f"{prefix}/kv_blocks", "ph": "C",
                               "ts": ts, "pid": pid, "tid": 0,
                               "args": {"free": r[10], "live": r[11],
                                        "shared": max(0, r[12])}})
            # speculative-decoding track: only spec engines emit it
            # (len guard: pre-PR-11 tuples are 16 fields)
            if len(r) > 17 and r[16] >= 0:
                events.append({"name": f"{prefix}/spec", "ph": "C",
                               "ts": ts, "pid": pid, "tid": 0,
                               "args": {"proposed": r[16],
                                        "accepted": r[17]}})
            # tenant-accounting track: only cost-ledger engines emit it
            # (len guard: pre-ledger tuples are 20 fields)
            if len(r) > 21 and r[21] >= 0:
                events.append({"name": f"{prefix}/tenants", "ph": "C",
                               "ts": ts, "pid": pid, "tid": 0,
                               "args": {"kv_block_s": r[20],
                                        "live": r[21]}})
        return events

    def merge_chrome(self, doc: dict) -> dict:
        """Merge this recorder's counter tracks into a span-export
        document (``trace.export_chrome()``), keeping the event list
        time-sorted (a stable sort preserves B/E emission order at equal
        timestamps, which the export's nesting contract relies on)."""
        events = list(doc.get("traceEvents", []))
        events.extend(self.chrome_counter_events())
        events.sort(key=lambda e: e["ts"])
        doc["traceEvents"] = events
        return doc

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"capacity": self.capacity, "retained": self._n,
                    "total": self.total}
