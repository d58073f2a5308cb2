"""From a profiler trace to numbers: the one trace reduction.

``Tracer`` turns the JAX profiler on around the measured window and
reads the ``.xplane.pb`` it leaves with ``jax.profiler.ProfileData``.
``load_events`` flattens that into plain rows, and ``reduce_events``
works on rows only, so the arithmetic is checked off the chip on a
small recorded trace (``benchmarks/fixtures/``, ``selfcheck.py``).

What a TPU trace holds (looked at by hand on the v5e, PR 24): one plane
per chip, ``/device:TPU:<n>``, with a line ``XLA Modules`` (one event
per run of a compiled program, named ``jit_<fn>(<fingerprint>)``) and a
line ``XLA Ops`` (one event per HLO op run, nested: a ``while`` covers
its body's ops); host threads are lines of the plane ``/host:CPU`` and
carry the benchmark's own ``TraceAnnotation`` spans (``bench.*``).
"""

from __future__ import annotations

import glob
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE, OP_LINE = "XLA Modules", "XLA Ops"
# HLO ops that move data between chips
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|send|recv)([-.]|$)")
SPAN_PREFIX = "bench."


def load_events(xplane_path: str) -> list:
    """Rows ``[plane, line, name, start_ns, duration_ns]`` of the device
    planes' module and op lines and of the host's ``bench.*`` spans."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    rows = []
    for plane in data.planes:
        on_device = DEVICE_PLANE.match(plane.name) is not None
        if not on_device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if on_device and line.name not in (MODULE_LINE, OP_LINE):
                continue
            for ev in line.events:
                if on_device or ev.name.startswith(SPAN_PREFIX):
                    rows.append([plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)])
    return rows


def union_ns(intervals) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo, hi) -> list:
    """Idle ``(start, end)`` stretches of ``[lo, hi]`` that no interval
    covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def leaf_ops(op_rows) -> list:
    """Ops of one ``XLA Ops`` line that cover no other op: a ``while``
    or a ``call`` holds its body's ops, and summing both counts the
    body twice. Rows are ``(name, start, end)``."""
    rows = sorted(op_rows, key=lambda r: (r[1], -r[2]))
    leaves, stack = [], []
    for row in rows:
        # what ended before this row starts, or does not hold it whole,
        # is no parent of it
        while stack and (row[1] >= stack[-1][0][2]
                         or row[2] > stack[-1][0][2]):
            top, has_child = stack.pop()
            if not has_child:
                leaves.append(top)
        if stack:
            stack[-1][1] = True
        stack.append([row, False])
    leaves += [top for top, has_child in stack if not has_child]
    return leaves


def op_label(event_name: str) -> str:
    """An op event's name is its whole HLO line. Keep the op's name, its
    result and its operands' shapes; drop layouts, operand names and
    attributes: ``fusion.200 bf16[3000000,300] fusion(bf16[3000000,300],
    s32[65536], bf16[65536,300])``. The shapes are what tells a table
    scatter from a row gather."""
    text = event_name.split("), ")[0]
    text = re.sub(r"\{[^{}]*\}", "", text)
    text = re.sub(r" %[\w.\-]+", "", text)
    text = text.replace(" = ", " ").lstrip("%")
    if "(" in text and not text.endswith(")"):
        text += ")"
    return text[:160]


def program_name(event_name: str) -> str:
    """``jit_fused(1234567)`` -> ``jit_fused``."""
    return event_name.split("(")[0]


def reduce_events(rows, window_s: float) -> dict:
    """The numbers every reader takes from a trace.

    ``busy_s``: per chip the union of its op intervals (of its module
    intervals where a trace has no op line), averaged over the chips.
    ``window_s``: the span of the host's ``bench.window`` annotation,
    else the caller's clock. ``programs``: device seconds and runs per
    compiled program, summed over chips. ``ops``: device seconds per
    leaf op name, summed over chips. ``collective_s`` and
    ``collective_exposed_s``: time in collective ops, and the part of it
    during which no other op ran on that chip, both averaged over chips.
    ``breakdown``: the contract's two short lists."""
    chips = {}
    spans = []
    for plane, line, name, start, dur in rows:
        if DEVICE_PLANE.match(plane):
            chip = chips.setdefault(plane, {MODULE_LINE: [], OP_LINE: []})
            chip[line].append((name, start, start + dur))
        elif name.startswith(SPAN_PREFIX):
            spans.append((name, start, start + dur))
    window = [s for s in spans if s[0] == SPAN_PREFIX + "window"]
    if window:
        lo, hi = window[0][1], window[0][2]
        window_s = (hi - lo) / 1e9
    else:
        lo, hi = float("-inf"), float("inf")

    def clip(events):
        return [(n, max(s, lo), min(e, hi)) for n, s, e in events
                if e > lo and s < hi]

    programs, ops = {}, {}
    busy, coll, exposed, idle_gaps = [], [], [], []
    for plane in sorted(chips):
        mods, op_rows = clip(chips[plane][MODULE_LINE]), \
            clip(chips[plane][OP_LINE])
        for name, s, e in mods:
            p = programs.setdefault(program_name(name), {"s": 0.0, "runs": 0})
            p["s"] += (e - s) / 1e9
            p["runs"] += 1
        leaves = leaf_ops(op_rows)
        for name, s, e in leaves:
            label = op_label(name)
            ops[label] = ops.get(label, 0.0) + (e - s) / 1e9
        cover = [(s, e) for _, s, e in (leaves or mods)]
        busy.append(union_ns(cover) / 1e9)
        c_iv = [(s, e) for n, s, e in leaves if COLLECTIVE.match(n)]
        o_iv = [(s, e) for n, s, e in leaves if not COLLECTIVE.match(n)]
        coll.append(union_ns(c_iv) / 1e9)
        exposed.append((union_ns(c_iv + o_iv) - union_ns(o_iv)) / 1e9)
        if plane == sorted(chips)[0] and window:
            idle_gaps = gaps(cover, lo, hi)
    n = max(len(chips), 1)
    return {
        "chips": len(chips),
        "busy_s": sum(busy) / n,
        "window_s": window_s,
        "programs": programs,
        "ops": ops,
        "collective_s": sum(coll) / n,
        "collective_exposed_s": sum(exposed) / n,
        "spans": _span_totals(spans),
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": _name_gaps(idle_gaps, spans)[:10],
        },
    }


def _span_totals(spans) -> dict:
    out = {}
    for name, s, e in spans:
        rec = out.setdefault(name, {"s": 0.0, "n": 0})
        rec["s"] += (e - s) / 1e9
        rec["n"] += 1
    return out


def _name_gaps(idle, spans) -> list:
    """Idle seconds by what the host was doing: each gap goes to the
    innermost ``bench.*`` span (the shortest) that covers its middle."""
    inner = sorted((s for s in spans if s[0] != SPAN_PREFIX + "window"),
                   key=lambda s: s[2] - s[1])
    by_name = {}
    for g_s, g_e in idle:
        mid = (g_s + g_e) / 2
        name = next((n for n, s, e in inner if s <= mid <= e),
                    "outside any bench span")
        by_name[name] = by_name.get(name, 0.0) + (g_e - g_s) / 1e9
    return [[k, v] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])]


class Tracer:
    """The profiler around one window. The trace directory is fixed,
    inside the checkout, emptied before and after: a run leaves nothing
    behind but the compile cache."""

    def __init__(self, directory: str) -> None:
        self.directory = directory
        self._window = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        jax.profiler.start_trace(self.directory)
        self._window = jax.profiler.TraceAnnotation(SPAN_PREFIX + "window")
        self._window.__enter__()

    def stop_and_reduce(self, window_s: float) -> dict:
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler left no .xplane.pb")
        rows = load_events(paths[0])
        shutil.rmtree(self.directory, ignore_errors=True)
        return reduce_events(rows, window_s)


def span(name: str):
    """A host span on the profiler's clock; free when no trace runs."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
