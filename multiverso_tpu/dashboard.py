"""Named timing monitors + process-global dashboard.

TPU-native equivalent of the reference observability layer
(``include/multiverso/dashboard.h:16-73``, ``src/dashboard.cpp:14-45`` in the
Multiverso reference): named ``Monitor`` timers (count / total ms / average)
registered into a process-global ``Dashboard``, a ``monitor(name)`` context
manager replacing the ``MONITOR_BEGIN/END`` macros, ``Dashboard.watch`` by
name and ``Dashboard.display`` at shutdown.

On TPU the interesting spans are host-side walls around dispatched programs;
``monitor(..., block=True)`` additionally calls
``jax.block_until_ready`` on a result so the span covers device execution,
not just async dispatch.
"""

from __future__ import annotations

import json
import math
import re
import threading
from .analysis import lockwatch
import time
from contextlib import contextmanager
from typing import Any, Dict, Iterator, List, Optional


class Timer:
    """Wall-clock start/elapse timer (reference ``util/timer.h:8-24``)."""

    def __init__(self) -> None:
        self.start()

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def elapse_ms(self) -> float:
        return (time.perf_counter() - self._t0) * 1e3


class Monitor:
    """Accumulating named timer (reference ``dashboard.h:26-57``).

    Start timestamps are thread-local so concurrent spans on the same
    monitor name don't clobber each other's begin().
    """

    def __init__(self, name: str, register: bool = True) -> None:
        self.name = name
        self.count = 0
        self.total_ms = 0.0
        self._local = threading.local()
        self._lock = lockwatch.lock("dashboard.Monitor._lock")
        if register:
            Dashboard.add_monitor(self)

    def begin(self) -> None:
        self._local.t0 = time.perf_counter()

    def end(self) -> None:
        t0 = getattr(self._local, "t0", None)
        if t0 is None:
            return
        elapsed = (time.perf_counter() - t0) * 1e3
        self._local.t0 = None
        self.record(elapsed)

    def record(self, elapsed_ms: float) -> None:
        """Fold an externally-measured duration (e.g. a cross-process
        publish->apply latency carried in a wire record)."""
        with self._lock:
            self.count += 1
            self.total_ms += elapsed_ms

    def average_ms(self) -> float:
        with self._lock:
            return self.total_ms / self.count if self.count else 0.0

    def info_string(self) -> str:
        with self._lock:
            avg = self.total_ms / self.count if self.count else 0.0
            return (
                f"[{self.name}] count = {self.count} total = {self.total_ms:.3f} ms "
                f"avg = {avg:.3f} ms"
            )


# -- mergeable log-bucket export ---------------------------------------------
#
# Exact sample windows cannot be merged across processes (shipping 65536
# floats per histogram per report interval would BE the fleet's traffic), so
# the fleet observability plane ships log-bucketed digests instead
# (DDSketch/Prometheus-native-histogram shape): bucket i holds samples in
# (BUCKET_BASE**i, BUCKET_BASE**(i+1)], merge = per-index count addition,
# and any percentile read off merged counts returns the containing bucket's
# geometric midpoint BUCKET_BASE**(i + 0.5).
#
# Error bound: a sample in bucket i is within a factor of BUCKET_BASE**0.5
# of that midpoint, so every percentile-from-buckets value is within
# BUCKET_REL_ERROR (= BUCKET_BASE**0.5 - 1, ~9.05% at base 2**0.25) of the
# exact nearest-rank percentile over the pooled samples — bucketing is
# monotone, so the rank-r sample of the pooled window lands in exactly the
# bucket the merged cumulative walk stops in (tests assert the bound on
# randomized multi-node splits). Values <= 0 land in a dedicated "zero"
# bucket that sorts below every indexed one and reads back as 0.0.

BUCKET_BASE = 2 ** 0.25
BUCKET_REL_ERROR = BUCKET_BASE ** 0.5 - 1
_BUCKET_LOG = math.log(BUCKET_BASE)


def bucket_index(value_ms: float) -> Optional[int]:
    """Log-bucket index for one sample (None = the zero bucket)."""
    if value_ms <= 0.0:
        return None
    return math.floor(math.log(value_ms) / _BUCKET_LOG)


def bucket_value(index: int) -> float:
    """The bucket's representative: the geometric midpoint of its edges."""
    return BUCKET_BASE ** (index + 0.5)


def merge_buckets(exports: List[Optional[Dict[str, Any]]]) -> Dict[str, Any]:
    """Sum per-index counts across node exports (:meth:`Histogram.buckets`
    dicts; ``None`` entries — nodes without that histogram — are skipped).
    Counts key as strings because the exports ride JSON wire records."""
    counts: Dict[str, int] = {}
    zero = 0
    count = 0
    for ex in exports:
        if not ex:
            continue
        zero += int(ex.get("zero", 0))
        count += int(ex.get("count", 0))
        for k, n in ex.get("counts", {}).items():
            counts[str(k)] = counts.get(str(k), 0) + int(n)
    return {"base": BUCKET_BASE, "count": count, "zero": zero,
            "counts": counts}


def bucket_percentile(export: Dict[str, Any], p: float) -> float:
    """Nearest-rank percentile over a (possibly merged) bucket export —
    same rank formula as :meth:`Histogram._rank`, walked over cumulative
    bucket counts, returning the containing bucket's midpoint (so the
    result is within :data:`BUCKET_REL_ERROR` of the pooled-sample
    truth)."""
    counts = export.get("counts", {})
    zero = int(export.get("zero", 0))
    n = zero + sum(int(v) for v in counts.values())
    if n == 0:
        return 0.0
    rank = min(n - 1, max(0, int(round(p / 100.0 * (n - 1)))))
    if rank < zero:
        return 0.0
    seen = zero
    for idx in sorted(int(k) for k in counts):
        seen += int(counts[str(idx)])
        if rank < seen:
            return bucket_value(idx)
    return bucket_value(max(int(k) for k in counts))   # pragma: no cover


def bucket_breach_frac(export: Dict[str, Any], threshold_ms: float) -> float:
    """Fraction of the bucketed window above ``threshold_ms`` (the fleet
    SLO burn numerator). Bucket-granular: a bucket counts as breaching
    when its representative midpoint exceeds the threshold, so the
    answer is exact to within the one bucket straddling the target."""
    counts = export.get("counts", {})
    n = int(export.get("zero", 0)) + sum(int(v) for v in counts.values())
    if n == 0:
        return 0.0
    over = sum(int(v) for k, v in counts.items()
               if bucket_value(int(k)) > threshold_ms)
    return over / n


class Histogram:
    """Bounded latency histogram: count/percentiles over a sliding window.

    The serving layer's per-reply latency sink (p50/p95/p99 + QPS need a
    distribution, not the Monitor's running mean). Keeps the most recent
    ``window`` samples in a ring — old traffic ages out, so percentiles
    track the CURRENT load regime, and memory stays bounded under
    sustained QPS. Thread-safe; registered in the Dashboard next to the
    Monitors so ``display()`` shows both.
    """

    WINDOW = 65536

    def __init__(self, name: str, window: int = WINDOW,
                 register: bool = True) -> None:
        self.name = name
        self.count = 0                      # lifetime samples (QPS numerator)
        self._buf = [0.0] * int(window)
        self._n = 0                         # filled slots (<= window)
        self._pos = 0                       # next write slot
        self._lock = lockwatch.lock("dashboard.Histogram._lock")
        if register:
            Dashboard.add_histogram(self)

    def record(self, value_ms: float) -> None:
        with self._lock:
            self.count += 1
            self._buf[self._pos] = float(value_ms)
            self._pos = (self._pos + 1) % len(self._buf)
            self._n = min(self._n + 1, len(self._buf))

    def reset(self) -> None:
        """Drop retained samples (benches: exclude warmup compiles from
        the measured distribution)."""
        with self._lock:
            self.count = 0
            self._n = 0
            self._pos = 0

    def _window(self):
        """ONE lock acquisition -> (lifetime count, sorted live window).
        The single source of the ring-unwrap + sort both percentile
        consumers share (a wrap-handling fix lands in both). Only the
        COPY happens under the lock: the O(n log n) sort of a full
        65536-slot window would otherwise stall every concurrent
        ``record`` on the serving hot path each time a poller (now
        including the periodic ``MetricsExporter``) asks for a summary."""
        with self._lock:
            n = self._n
            count = self.count
            # unwrapped: slots [0, n) are the live samples; wrapped: all are
            data = (list(self._buf) if n == len(self._buf)
                    else self._buf[:n])
        data.sort()
        return count, data

    @staticmethod
    def _rank(data, p: float) -> float:
        """Nearest-rank percentile over a sorted window."""
        n = len(data)
        return data[min(n - 1, max(0, int(round(p / 100.0 * (n - 1)))))]

    def percentiles(self, ps) -> Dict[float, float]:
        """Nearest-rank percentiles over the retained window in ONE sort
        (0s if empty) — summary()/stats() pollers would otherwise pay a
        full sort per percentile while contending with record()."""
        _, data = self._window()
        if not data:
            return {p: 0.0 for p in ps}
        return {p: self._rank(data, p) for p in ps}

    def percentile(self, p: float) -> float:
        return self.percentiles((p,))[p]

    def window_stats(self, p: float, threshold_ms: float, window=None):
        """``(window n, pXX, fraction of window above threshold)`` in ONE
        sort — the SLO tracker's read (a separate percentile + breach
        scan would pay two sorts and could straddle a wrap). Pass a
        ``window`` (an already-sorted sample list, e.g. the one
        ``Dashboard.snapshot()`` just paid for this histogram's own
        summary row) to skip the copy-under-lock + re-sort entirely."""
        import bisect

        data = self._window()[1] if window is None else window
        if not data:
            return 0, 0.0, 0.0
        frac = 1.0 - bisect.bisect_right(data, threshold_ms) / len(data)
        return len(data), self._rank(data, p), frac

    def summary(self) -> Dict[str, float]:
        """count + nearest-rank p50/p95/p99 + mean/max over the window.

        mean and max ride along because percentile triage alone can't
        rank outliers: a p99 says where the tail STARTS, the max says
        how bad the worst request actually was, and mean-vs-p50 skew is
        the cheapest "long tail present" signal. Count and window are
        read under ONE lock acquisition so the summary is internally
        consistent even while ``record`` hammers concurrently.
        """
        return self._summarize(*self._window())[0]

    def _summarize(self, count, data):
        """``(summary dict, sorted window)`` from one ``_window()`` read —
        ``Dashboard.snapshot()`` hands the window on to this histogram's
        SLO row so the pair shares one copy+sort AND describes the same
        samples."""
        if not data:
            return ({"count": count, "p50_ms": 0.0, "p95_ms": 0.0,
                     "p99_ms": 0.0, "mean_ms": 0.0, "max_ms": 0.0}, data)
        return ({
            "count": count,
            "p50_ms": self._rank(data, 50),
            "p95_ms": self._rank(data, 95),
            "p99_ms": self._rank(data, 99),
            "mean_ms": sum(data) / len(data),
            "max_ms": data[-1],
        }, data)

    def buckets(self) -> Dict[str, Any]:
        """Log-bucket export of the retained window (the mergeable form
        the fleet observability plane ships): ``{"base", "count"
        (lifetime), "n" (window), "zero", "counts": {str(index):
        count}}``. One window copy, no sort; see the module-level
        bucket notes for the merge rule and the documented
        :data:`BUCKET_REL_ERROR` percentile bound."""
        with self._lock:
            count = self.count
            data = (list(self._buf) if self._n == len(self._buf)
                    else self._buf[: self._n])
        counts: Dict[str, int] = {}
        zero = 0
        for v in data:
            idx = bucket_index(v)
            if idx is None:
                zero += 1
            else:
                key = str(idx)
                counts[key] = counts.get(key, 0) + 1
        return {"base": BUCKET_BASE, "count": count, "n": len(data),
                "zero": zero, "counts": counts}

    def info_string(self) -> str:
        s = self.summary()
        return (f"[{self.name}] count = {int(s['count'])} "
                f"p50 = {s['p50_ms']:.3f} ms p95 = {s['p95_ms']:.3f} ms "
                f"p99 = {s['p99_ms']:.3f} ms mean = {s['mean_ms']:.3f} ms "
                f"max = {s['max_ms']:.3f} ms")


class Gauge:
    """Last-value instrument: a point-in-time level, not a distribution.

    The serving engine's occupancy/throughput readouts (slots in use,
    decode tokens/sec) are levels — a histogram of them would average
    away exactly the saturation signal an operator looks for. ``set``
    overwrites; ``get`` reads the latest value.
    """

    def __init__(self, name: str, register: bool = True) -> None:
        self.name = name
        self._value = 0.0
        self._lock = lockwatch.lock("dashboard.Gauge._lock")
        if register:
            Dashboard.add_gauge(self)

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    def get(self) -> float:
        with self._lock:
            return self._value

    def info_string(self) -> str:
        return f"[{self.name}] value = {self.get():.3f}"


class Counter:
    """Monotonic event counter: things that HAPPENED, never un-happen.

    The Monitor measures durations and the Gauge levels; neither fits
    "requests shed", "idle wakeups", "tokens emitted" — monotonic
    totals whose interval-deltas (``MetricsExporter``) become rates.
    Maps to the Prometheus ``counter`` type in the text exposition.
    """

    def __init__(self, name: str, register: bool = True) -> None:
        self.name = name
        self._value = 0
        self._lock = lockwatch.lock("dashboard.Counter._lock")
        if register:
            Dashboard.add_counter(self)

    def inc(self, n: int = 1) -> None:
        if n < 0:
            raise ValueError(f"Counter {self.name!r}: negative increment {n}")
        with self._lock:
            self._value += n

    def get(self) -> int:
        with self._lock:
            return self._value

    def info_string(self) -> str:
        return f"[{self.name}] total = {self.get()}"


class SLO:
    """Windowed latency objective over a registered :class:`Histogram`.

    ``source`` names the histogram (``SERVE_TTFT[lm]``); the objective is
    "the windowed p<percentile> stays under ``target_ms``". ``summary()``
    reports the current percentile, the fraction of the window breaching
    the target, and the **burn rate** — breach fraction over the error
    budget ``1 - percentile/100`` (burn > 1 means the tail is eating its
    budget faster than allowed; the SRE alarm convention). Rolling by
    construction: the histogram window ages old traffic out, so burn
    tracks the CURRENT regime, not the lifetime average.
    """

    def __init__(self, source: str, target_ms: float,
                 percentile: float = 99.0, register: bool = True) -> None:
        self.source = source
        self.target_ms = float(target_ms)
        self.percentile = float(percentile)
        self.name = f"SLO_P{percentile:g}[{source}]"
        if register:
            Dashboard.add_slo(self)

    def summary(self, window=None) -> Dict[str, float]:
        hist = Dashboard.get_or_create_histogram(self.source)
        n, value, frac = hist.window_stats(self.percentile, self.target_ms,
                                           window=window)
        budget = max(1.0 - self.percentile / 100.0, 1e-9)
        return {
            "target_ms": self.target_ms,
            "percentile": self.percentile,
            "window": n,
            "value_ms": value,
            "breach_frac": frac,
            "burn": frac / budget,
            "ok": 0 if (n and value > self.target_ms) else 1,
        }

    def info_string(self) -> str:
        s = self.summary()
        state = "OK" if s["ok"] else "BURNING"
        return (f"[{self.name}] p{self.percentile:g} = {s['value_ms']:.3f} "
                f"ms target = {self.target_ms:.3f} ms burn = "
                f"{s['burn']:.2f} ({state})")


class Dashboard:
    """Process-global monitor registry (reference ``dashboard.h:16-24``)."""

    _monitors: Dict[str, Monitor] = {}
    _histograms: Dict[str, "Histogram"] = {}
    _gauges: Dict[str, "Gauge"] = {}
    _counters: Dict[str, "Counter"] = {}
    _slos: Dict[str, "SLO"] = {}
    # running reporter/watchdog threads (anything with .detach());
    # reset() stops them so tests can't leak threads across each other
    _reporters: List[Any] = []
    _lock = lockwatch.lock("dashboard.Dashboard._lock")

    @classmethod
    def add_monitor(cls, mon: Monitor) -> None:
        with cls._lock:
            cls._monitors[mon.name] = mon

    @classmethod
    def add_histogram(cls, hist: "Histogram") -> None:
        with cls._lock:
            cls._histograms[hist.name] = hist

    @classmethod
    def add_gauge(cls, gauge: "Gauge") -> None:
        with cls._lock:
            cls._gauges[gauge.name] = gauge

    @classmethod
    def add_counter(cls, counter: "Counter") -> None:
        with cls._lock:
            cls._counters[counter.name] = counter

    @classmethod
    def add_slo(cls, slo: "SLO") -> None:
        with cls._lock:
            cls._slos[slo.name] = slo

    @classmethod
    def set_slo(cls, source: str, target_ms: float,
                percentile: float = 99.0) -> "SLO":
        """Declare (or re-target) a latency objective over histogram
        ``source``; its burn status rides every ``snapshot()``."""
        name = f"SLO_P{percentile:g}[{source}]"
        with cls._lock:
            slo = cls._slos.get(name)
        if slo is None:
            slo = SLO(source, target_ms, percentile)
        else:
            slo.target_ms = float(target_ms)
        return slo

    @classmethod
    def attach_reporter(cls, reporter: Any) -> None:
        """Track a running reporter thread (MetricsExporter, watchdog);
        ``reset()`` detaches and stops whatever is still attached."""
        with cls._lock:
            if reporter not in cls._reporters:
                cls._reporters.append(reporter)

    @classmethod
    def detach_reporter(cls, reporter: Any) -> None:
        with cls._lock:
            if reporter in cls._reporters:
                cls._reporters.remove(reporter)

    @classmethod
    def get_or_create_histogram(cls, name: str) -> "Histogram":
        with cls._lock:
            hist = cls._histograms.get(name)
            if hist is None:
                hist = Histogram(name, register=False)
                cls._histograms[name] = hist
            return hist

    @classmethod
    def get_or_create_gauge(cls, name: str) -> "Gauge":
        with cls._lock:
            gauge = cls._gauges.get(name)
            if gauge is None:
                gauge = Gauge(name, register=False)
                cls._gauges[name] = gauge
            return gauge

    @classmethod
    def get_or_create(cls, name: str) -> Monitor:
        with cls._lock:
            mon = cls._monitors.get(name)
            if mon is None:
                mon = Monitor(name, register=False)
                cls._monitors[name] = mon
            return mon

    @classmethod
    def get_or_create_counter(cls, name: str) -> "Counter":
        with cls._lock:
            counter = cls._counters.get(name)
            if counter is None:
                counter = Counter(name, register=False)
                cls._counters[name] = counter
            return counter

    @classmethod
    def watch(cls, name: str) -> str:
        """Live one-liner for ANY registered instrument. Resolves every
        kind — ``watch("SERVE_TTFT[lm]")`` must report the histogram,
        not "not monitored" (it used to check Monitors only)."""
        with cls._lock:
            inst = (cls._monitors.get(name) or cls._histograms.get(name)
                    or cls._gauges.get(name) or cls._counters.get(name)
                    or cls._slos.get(name))
        return inst.info_string() if inst else f"[{name}] not monitored"

    @classmethod
    def stats(cls, name: str) -> Optional[Dict[str, float]]:
        with cls._lock:
            mon = cls._monitors.get(name)
            hist = cls._histograms.get(name)
            gauge = cls._gauges.get(name)
            counter = cls._counters.get(name)
            slo = cls._slos.get(name)
        if mon is not None:
            return {"count": mon.count, "total_ms": mon.total_ms,
                    "avg_ms": mon.average_ms()}
        if hist is not None:
            return hist.summary()
        if gauge is not None:
            return {"value": gauge.get()}
        if counter is not None:
            return {"value": counter.get()}
        if slo is not None:
            return slo.summary()
        return None

    @classmethod
    def snapshot(cls) -> Dict[str, Dict[str, Any]]:
        """EVERY instrument's current state as one plain dict.

        ``{name: {"type": kind, ...stats}}`` — JSON-serializable floats
        and ints only, so the same object feeds the JSON-lines reporter,
        the Prometheus renderer, and bench archives without per-sink
        formats.
        """
        with cls._lock:
            monitors = list(cls._monitors.values())
            histograms = list(cls._histograms.values())
            gauges = list(cls._gauges.values())
            counters = list(cls._counters.values())
            slos = list(cls._slos.values())
        out: Dict[str, Dict[str, Any]] = {}
        for m in monitors:
            out[m.name] = {"type": "monitor", "count": m.count,
                           "total_ms": m.total_ms, "avg_ms": m.average_ms()}
        windows: Dict[str, list] = {}
        for h in histograms:
            summary, windows[h.name] = h._summarize(*h._window())
            out[h.name] = {"type": "histogram", **summary}
        for g in gauges:
            out[g.name] = {"type": "gauge", "value": g.get()}
        for c in counters:
            out[c.name] = {"type": "counter", "value": c.get()}
        for s in slos:
            # reuse the source histogram's sorted window: one copy+sort
            # per histogram per snapshot, and the SLO row describes the
            # SAME samples as the histogram row above it
            out[s.name] = {"type": "slo",
                           **s.summary(window=windows.get(s.source))}
        return out

    @classmethod
    def display(cls, emit=None) -> str:
        with cls._lock:
            monitors = list(cls._monitors.values())
            histograms = list(cls._histograms.values())
            gauges = list(cls._gauges.values())
            counters = list(cls._counters.values())
            slos = list(cls._slos.values())
        lines = ["--------------Dashboard--------------"]
        lines += [m.info_string() for m in monitors]
        lines += [h.info_string() for h in histograms]
        lines += [g.info_string() for g in gauges]
        lines += [c.info_string() for c in counters]
        lines += [s.info_string() for s in slos]
        text = "\n".join(lines)
        if emit is None:
            from .log import Log
            emit = Log.info
        emit("%s", text)
        return text

    @classmethod
    def reset(cls) -> None:
        """Drop every instrument AND stop any attached reporter thread
        (MetricsExporter, engine watchdogs): a test that resets the
        dashboard must not inherit a prior test's reporter still
        snapshotting (or a watchdog still polling a dead engine).
        Reporters are popped under the lock but stopped OUTSIDE it —
        their threads may be mid-``snapshot()`` and need the lock to
        finish before they can join."""
        with cls._lock:
            cls._monitors.clear()
            cls._histograms.clear()
            cls._gauges.clear()
            cls._counters.clear()
            cls._slos.clear()
            reporters = list(cls._reporters)
            cls._reporters.clear()
        for reporter in reporters:
            try:
                reporter.detach()
            except Exception as exc:    # pragma: no cover - defensive
                from .log import Log
                Log.error("dashboard reset: reporter detach failed: %s", exc)


@contextmanager
def monitor(name: str, block_on: Any = None) -> Iterator[Monitor]:
    """Span context manager replacing MONITOR_BEGIN/END.

    If ``block_on`` is supplied (a jax.Array / pytree produced inside the
    span), it is blocked on before the span closes so device time is counted.
    """
    mon = Dashboard.get_or_create(name)
    mon.begin()
    try:
        yield mon
    finally:
        if block_on is not None:
            import jax
            jax.block_until_ready(block_on)
        mon.end()


def monitored_block_until_ready(name: str, value: Any) -> Any:
    """Time a block_until_ready on ``value`` under monitor ``name``."""
    import jax

    mon = Dashboard.get_or_create(name)
    mon.begin()
    jax.block_until_ready(value)
    mon.end()
    return value


@contextmanager
def profile_trace(log_dir: str, name: str = "PROFILE") -> Iterator[Monitor]:
    """Capture an XLA profiler trace for the enclosed span.

    Observability tier above the reference's wall-clock Monitors (SURVEY
    §5.5: "no tracing spans"): wraps ``jax.profiler`` so the span's device
    timeline (HLO ops, HBM transfers, collective phases) lands in
    ``log_dir`` for TensorBoard/xprof, while a Dashboard monitor records
    the same span's wall time alongside the other counters.
    """
    import jax

    mon = Dashboard.get_or_create(name)
    mon.begin()
    jax.profiler.start_trace(log_dir)
    try:
        yield mon
    finally:
        jax.profiler.stop_trace()
        mon.end()


# -- metrics export ----------------------------------------------------------

# The ONE definition of which snapshot stats are monotonic, shared by the
# Prometheus renderer (# TYPE counter vs gauge) and the JSONL reporter's
# interval deltas — two hardcoded copies would drift and make the sinks
# disagree about which stats are rates.
_MONOTONE_STATS = frozenset({
    ("counter", "value"), ("monitor", "count"), ("monitor", "total_ms"),
    ("histogram", "count"),
})


def snapshot_deltas(prev: Optional[Dict[str, Dict[str, Any]]],
                    snap: Dict[str, Dict[str, Any]],
                    dt: Optional[float]) -> Dict[str, Dict[str, float]]:
    """Interval deltas of the monotonic stats between two snapshots —
    THE delta semantics, shared by :class:`MetricsExporter` and the
    fleet observability plane's per-node reports
    (``serving/obs_plane.py``), so the JSONL reporter and the wire can
    never drift on what counts as a rate.

    Covers the ``_MONOTONE_STATS`` fields only. An instrument whose
    monotonic stats went BACKWARDS (reset mid-interval) reports no
    delta rather than a negative rate; an instrument absent from
    ``prev`` (or whose type changed) is skipped for this interval and
    picked up on the next one."""
    if prev is None or not dt or dt <= 0:
        return {}
    deltas: Dict[str, Dict[str, float]] = {}
    for name, row in snap.items():
        last = prev.get(name)
        if last is None or last.get("type") != row.get("type"):
            continue
        kind = row.get("type")
        d: Dict[str, float] = {}
        for field, value in row.items():
            if (kind, field) not in _MONOTONE_STATS:
                continue
            diff = value - last.get(field, 0)
            if diff < 0:
                d = {}
                break               # instrument was reset mid-interval
            d[field] = diff
            d[f"{field}_per_s"] = diff / dt
        if d:
            deltas[name] = d
    return deltas


def _prom_split(name: str):
    """``SERVE_TTFT[lm]`` -> (``serve_ttft``, ``lm``); plain names pass
    through with no instance label. The bracket convention is how every
    per-model instrument in this codebase is named."""
    instance = None
    base = name
    if name.endswith("]") and "[" in name:
        base, _, rest = name.partition("[")
        instance = rest[:-1]
    metric = re.sub(r"[^a-zA-Z0-9_]", "_", base.lower()).strip("_")
    return metric or "unnamed", instance


def _prom_escape(value: str) -> str:
    return value.replace("\\", "\\\\").replace('"', '\\"').replace(
        "\n", "\\n")


def _prom_format(value: Any) -> str:
    # repr() floats round-trip exactly through float() — the renderer's
    # half of the snapshot-identity contract the tests assert
    return repr(float(value)) if isinstance(value, float) else str(value)


def render_prometheus(snapshot: Optional[Dict[str, Dict[str, Any]]] = None,
                      labels: Optional[Dict[str, str]] = None) -> str:
    """Prometheus text exposition of a :meth:`Dashboard.snapshot`.

    One sample per (instrument, stat field): the histogram
    ``SERVE_TTFT[lm]`` renders as ``mv_serve_ttft_p50_ms{name="...",
    instance="lm"} 1.25`` and so on. The full original instrument name
    always rides the ``name`` label, so the mapping is lossless (and the
    round-trip test can reconstruct the snapshot from the text).
    Monotonic stats (counter values, monitor/histogram counts,
    monitor total_ms) carry ``# TYPE counter``; everything else is a
    gauge. ``labels`` appends fixed extra labels to every sample — the
    fleet plane renders each node's registry with ``{"node": "<rank>"}``
    so one scrape surface covers the whole fleet without name
    collisions (``parse_prometheus`` tolerates the extra labels).
    """
    snap = Dashboard.snapshot() if snapshot is None else snapshot
    extra = "".join(f',{k}="{_prom_escape(str(v))}"'
                    for k, v in sorted((labels or {}).items()))
    families: Dict[str, List[str]] = {}
    family_type: Dict[str, str] = {}
    for name in sorted(snap):
        row = dict(snap[name])
        kind = row.pop("type", "gauge")
        metric, instance = _prom_split(name)
        for field in sorted(row):
            value = row[field]
            if not isinstance(value, (int, float)) or isinstance(value,
                                                                 bool):
                continue            # wire-merged rows may carry strings
            full = (f"mv_{metric}" if field == "value"
                    else f"mv_{metric}_{field}")
            monotone = (kind, field) in _MONOTONE_STATS
            sample_labels = f'name="{_prom_escape(name)}"'
            if instance is not None:
                sample_labels += f',instance="{_prom_escape(instance)}"'
            sample_labels += extra
            family_type.setdefault(full,
                                   "counter" if monotone else "gauge")
            families.setdefault(full, []).append(
                f"{full}{{{sample_labels}}} {_prom_format(value)}")
    lines: List[str] = []
    for full in sorted(families):
        lines.append(f"# TYPE {full} {family_type[full]}")
        lines.extend(families[full])
    return "\n".join(lines) + ("\n" if lines else "")


def parse_prometheus(text: str) -> Dict[str, Dict[str, float]]:
    """Inverse of :func:`render_prometheus` keyed by the ``name`` label:
    ``{instrument_name: {sample_name: value}}``. Used by the round-trip
    test and by anyone scraping the text sink without a Prometheus."""
    out: Dict[str, Dict[str, float]] = {}
    sample = re.compile(r'^(\w+)\{name="((?:[^"\\]|\\.)*)"[^}]*\} (\S+)$')
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = sample.match(line)
        if not m:
            continue
        full, name, value = m.groups()
        # unescape left-to-right (sequential .replace would corrupt a
        # literal backslash followed by 'n' into a newline)
        name = re.sub(r"\\(.)",
                      lambda g: {"n": "\n"}.get(g.group(1), g.group(1)),
                      name)
        out.setdefault(name, {})[full] = float(value)
    return out


class MetricsExporter:
    """Periodic metrics reporter: snapshot -> JSON-lines sink + deltas.

    Every ``interval_s`` (and on :meth:`stop`) it takes ONE
    ``Dashboard.snapshot()`` and appends one JSON line::

        {"ts": <epoch s>, "interval_s": <dt since last report or null>,
         "snapshot": {...}, "deltas": {name: {field: d, field_per_s: r}}}

    ``deltas`` cover the monotonic stats only (counter values,
    monitor/histogram counts, monitor total_ms): the interval-dt rates
    an operator actually plots, computed HERE so the sink needs no
    state. A snapshot whose monotonic stats went backwards (instrument
    reset) reports no delta for that instrument rather than a negative
    rate. :meth:`prometheus` renders the same snapshot for a scrape
    endpoint; both sinks see identical values by construction.
    """

    _MONOTONE = _MONOTONE_STATS

    def __init__(self, interval_s: float = 10.0, sink: Any = None,
                 emit=None) -> None:
        self.interval_s = float(interval_s)
        self._sink_path = sink if isinstance(sink, str) else None
        self._sink_file = sink if sink is not None and not isinstance(
            sink, str) else None
        self._emit = emit
        self._last: Optional[Dict[str, Dict[str, Any]]] = None
        self._last_ts: Optional[float] = None
        # interval math runs on the monotonic clock — a wall-clock step
        # (NTP) must not skew per-second delta rates; _last_ts is the
        # archived wall timestamp
        self._last_mono: Optional[float] = None
        # serializes snapshot+commit PAIRS across concurrent
        # report_once calls (see its docstring); distinct from _lock so
        # prometheus()/stop() never wait behind a registry sweep
        self._report_lock = lockwatch.lock(
            "dashboard.MetricsExporter._report_lock")
        self._lock = lockwatch.lock("dashboard.MetricsExporter._lock")
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.reports = 0

    # -- one report ---------------------------------------------------------
    def _deltas(self, snap: Dict[str, Dict[str, Any]],
                dt: Optional[float]) -> Dict[str, Dict[str, float]]:
        # the shared helper IS the semantics; this wrapper only binds the
        # exporter's last-snapshot state
        return snapshot_deltas(self._last, snap, dt)

    def report_once(self) -> dict:
        """Take one snapshot, compute interval deltas, write one line.

        ``_lock`` covers only the last-snapshot state, NOT the registry
        fan-out or the sink write: ``Dashboard.snapshot()`` acquires the
        registry lock plus every instrument's (locklint LK204 — holding
        ``_lock`` across it would serialize concurrent ``prometheus()``
        scrapes and ``stop()`` behind the whole sweep), and a stalled
        sink (full disk, hung NFS) must not block them either; an
        ``emit`` callback may safely call back into the exporter.

        ``_report_lock`` spans the snapshot+commit pair so concurrent
        calls (the reporter loop racing ``stop()``'s final report after
        a hung-sink join timeout) commit in snapshot order — without
        it, an older snapshot could commit as newest and the following
        report would double-count the interval its deltas re-span. It
        is touched by NOTHING else, so the LK204 concern above does not
        apply to it: scrapes and stop() never wait behind the sweep.
        """
        with self._report_lock:
            snap = Dashboard.snapshot()
            now = time.time()
            mono = time.monotonic()
            with self._lock:
                dt = ((mono - self._last_mono)
                      if self._last_mono is not None else None)
                record = {"ts": now, "interval_s": dt, "snapshot": snap,
                          "deltas": self._deltas(snap, dt)}
                self._last, self._last_ts = snap, now
                self._last_mono = mono
                self.reports += 1
        line = json.dumps(record)
        if self._sink_path is not None:
            with open(self._sink_path, "a") as f:
                f.write(line + "\n")
        elif self._sink_file is not None:
            self._sink_file.write(line + "\n")
        if self._emit is not None:
            self._emit(line)
        return record

    def prometheus(self) -> str:
        """Text exposition of the LAST reported snapshot (a scrape sees
        the same values the JSON line archived), or a fresh one before
        any report."""
        with self._lock:
            snap = self._last
        return render_prometheus(snap)

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "MetricsExporter":
        if self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._loop, name="mv-metrics", daemon=True)
        self._thread.start()
        Dashboard.attach_reporter(self)
        return self

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.report_once()
            except Exception as exc:    # pragma: no cover - sink errors
                from .log import Log
                Log.error("metrics exporter: report failed: %s", exc)

    def detach(self) -> None:
        """``Dashboard.reset()`` hook: stop WITHOUT a final report (the
        instruments were just cleared; archiving an empty snapshot over
        the sink's real data would only confuse the reader)."""
        self.stop(final_report=False)

    def stop(self, final_report: bool = True) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        Dashboard.detach_reporter(self)
        if final_report:
            try:
                self.report_once()
            except Exception as exc:
                # a dead sink at shutdown (disk full, hung mount) must
                # not abort the rest of Session teardown
                from .log import Log
                Log.error("metrics exporter: final report failed: %s", exc)
