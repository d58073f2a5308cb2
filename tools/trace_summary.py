"""Summarise an xprof trace by op/source — and explain per-request time.

Companion to ``dashboard.profile_trace`` (and any ``jax.profiler`` trace):
reads the ``*.trace.json.gz`` a capture writes and prints hardware-measured
device-op durations aggregated two ways —

* by SOURCE line (``file.py:123``) — where your program's time goes;
* by HLO op name — what XLA turned it into.

This is the analysis loop behind the README's per-op table: capture once
(``python tools/w2v_profile.py --trace DIR`` or ``with
profile_trace(DIR): ...``), then ``python tools/trace_summary.py DIR``.
A host clock around an asynchronous dispatch measures the enqueue; the
trace's ``device_duration_ps`` values come from the hardware counters
and are the device-time number.

``--host-trace FILE`` adds the REQUEST dimension (docs/OBSERVABILITY.md):
FILE is a Chrome trace JSON from ``multiverso_tpu.trace``
(``trace.export_chrome(FILE)``). Per request (one root span per
trace id) the report breaks host wall time into queue wait, admission/
prefill, batch execution and decode iterations — the stages that explain
a p99 outlier. Given BOTH a host trace and an xprof TRACE_DIR, the two
reports print one after the other and are NOT merged: the ring's clock
is the host's, a capture's counts from its own session's start. What
has to line up with device ops is a ``trace.phase``, in the capture.

Usage::

    python tools/trace_summary.py TRACE_DIR [--top 20] [--by op|source]
    python tools/trace_summary.py --host-trace serve.json [TRACE_DIR]
        [--top 20] [--sort total|queue] [--slo-ms 250]

``--slo-ms`` flags (``!``) and counts requests whose total exceeds the
objective; on tail-sampled captures (``-trace_tail``) a ``keep`` column
says why each retained trace survived the sampler (slo/error/head).
Ledger-enabled captures (``-cost_ledger``) add ``tenant``/``cost``
columns from each request's ``acct.request`` accounting span —
attribution and price next to the latency breakdown.
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import sys


def load_events(trace_dir: str):
    pattern = os.path.join(trace_dir, "**", "*.trace.json.gz")
    files = sorted(glob.glob(pattern, recursive=True))
    if not files:
        sys.exit(f"no *.trace.json.gz under {trace_dir}")
    events = []
    for path in files:
        with gzip.open(path) as f:
            events.extend(json.load(f).get("traceEvents", []))
    return events


def summarize(events, by: str = "source"):
    dur = collections.Counter()
    count = collections.Counter()
    label = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        args = e.get("args", {})
        if "device_duration_ps" not in args:
            continue
        name = e.get("name", "")
        if "while" in name or name.startswith("jit_"):
            continue   # wrapper events (while loops, whole-module jit
            #            executions) already include their children
        if by == "source":
            key = args.get("source", "")
            if not key:
                continue
            label.setdefault(key, set()).add(
                args.get("tf_op", "").split("/")[-1][:40])
        else:
            key = e.get("name", "?")
            label.setdefault(key, set()).add(
                args.get("source", "").split("/")[-1])
        dur[key] += int(args["device_duration_ps"]) / 1e9   # ps -> ms
        count[key] += 1
    return dur, count, label


def load_host_spans(path: str):
    """Rebuild spans from a ``multiverso_tpu.trace`` Chrome export:
    matched B/E pairs per (pid, tid) track -> span dicts."""
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc if isinstance(doc, list) else [])
    stacks: dict = {}
    spans = []
    for e in events:
        ph = e.get("ph")
        key = (e.get("pid"), e.get("tid"))
        if ph == "B":
            stacks.setdefault(key, []).append(e)
        elif ph == "E":
            stack = stacks.get(key)
            if not stack:
                continue
            b = stack.pop()
            args = b.get("args", {})
            spans.append({
                "name": b.get("name", "?"),
                "ts": float(b.get("ts", 0.0)),
                "dur": float(e.get("ts", 0.0)) - float(b.get("ts", 0.0)),
                "trace_id": args.get("trace_id"),
                "parent_id": args.get("parent_id"),
                # the recording process: os.getpid() in a single-node
                # export, the node rank in the obs-plane collector's
                # merged fleet doc — the report's grouping key half
                "node": e.get("pid"),
                "args": args,
            })
    return spans


# child span names folded into per-request report columns. NB
# decode.prefill_chunk spans lie INSIDE their decode.admit window —
# prefill_ms is the dispatch-side slice of admit_ms, not extra time
_STAGE_COLUMNS = (
    ("queue_ms", ("queue.wait",)),
    ("admit_ms", ("decode.admit",)),
    ("prefill_ms", ("decode.prefill_chunk",)),
    ("exec_ms", ("batch.exec",)),
    ("decode_ms", ("decode.iter",)),
)


def request_report(spans):
    """Per-request rows from host spans.

    A request = one root span (no parent_id) and every span sharing its
    **(node, trace id)** — node being the recording pid (the node rank
    in an obs-plane merged fleet doc). Grouping by trace id alone broke
    on multi-process documents: two nodes' trace ids can collide (the
    rows silently vanished under the != 1 roots guard), and a
    cross-process ``bus.publish``/``bus.apply`` pair SHARES one trace id
    by design — per-node grouping keeps each node's half its own row,
    and the ``node`` column says which replica served what.
    """
    by_trace: dict = {}
    for sp in spans:
        if sp["trace_id"] is not None:
            by_trace.setdefault((sp.get("node"), sp["trace_id"]),
                                []).append(sp)
    rows = []
    for (node, trace_id), group in by_trace.items():
        roots = [s for s in group if s["parent_id"] is None]
        if len(roots) != 1:
            continue            # cross-process fragments / partial capture
        root = roots[0]
        row = {
            "trace_id": trace_id,
            "node": node,
            "name": root["name"],
            "model": root["args"].get("model", ""),
            "total_ms": root["dur"] / 1e3,
            "iters": sum(s["name"] == "decode.iter" for s in group),
            # present on tail-sampled captures: WHY this trace survived
            # the sampler (slo / error / head) — a report full of "head"
            # rows means the SLO never breached
            "keep": root["args"].get("tail_keep", ""),
        }
        for col, names in _STAGE_COLUMNS:
            row[col] = sum(s["dur"] for s in group
                           if s["name"] in names) / 1e3
        # paged-KV admissions annotate their reservation: blocks held
        # and the pool's free count at admit time — a fat queue_ms next
        # to a small pool_free says the request waited for BLOCKS, not
        # for a slot
        admits = [s for s in group if s["name"] == "decode.admit"]
        if admits and "blocks" in admits[0]["args"]:
            row["blocks"] = admits[0]["args"]["blocks"]
            row["pool_free"] = admits[0]["args"].get("pool_free")
        # prefix-cache engines annotate the admit span with the blocks
        # matched and the prefill tokens they saved: a near-zero
        # admit/prefill column next to a fat "saved" one says this
        # request's TTFT came from the cache, not from prefill work
        if admits and "prefix_hit_blocks" in admits[0]["args"]:
            row["prefix_hit_blocks"] = admits[0]["args"]["prefix_hit_blocks"]
            row["prefill_tokens_saved"] = admits[0]["args"].get(
                "prefill_tokens_saved", 0)
        # sharded-decode engines annotate the admit span with the decode
        # mesh width: the report then says which tensor-parallel config
        # served each row (replicated engines omit it — no column)
        if admits and "decode_tp" in admits[0]["args"]:
            row["decode_tp"] = admits[0]["args"]["decode_tp"]
        # sequence-parallel engines (-prefill_sp) annotate every
        # prefill_chunk span with the routing decision: the report's sp
        # column then says which prompts ran the seqpar program (and
        # with which backend) versus riding the single-lane path under
        # the threshold ("off"); engines without the flag omit the
        # column entirely
        sp_chunks = [s for s in group
                     if s["name"] == "decode.prefill_chunk"
                     and "sp" in s["args"]]
        if sp_chunks:
            row["sp"] = (sp_chunks[0]["args"].get("sp_backend", "?")
                         if any(c["args"].get("sp") for c in sp_chunks)
                         else "off")
        # quantized-KV engines annotate the admit span with the pool
        # encoding: the report then says which requests were served off
        # int8 pools (fp engines omit it — no column), the first thing
        # to check when a fleet's outputs drift between replicas
        if admits and "kv_quant" in admits[0]["args"]:
            row["kv_quant"] = admits[0]["args"]["kv_quant"]
        # preempted-and-resumed requests: decode.preempt spans count the
        # evictions and the resume's admit span carries the running
        # total — a fat total_ms next to a nonzero preempt column says
        # this request paid for someone else's burst
        preempts = sum(s["name"] == "decode.preempt" for s in group)
        resumed = [a for a in admits if "preempted" in a["args"]]
        if preempts or resumed:
            row["preempted"] = (resumed[-1]["args"]["preempted"]
                                if resumed else preempts)
        # disaggregated requests: the decode.admit and kv.transfer
        # spans carry the transfer-plane accounting — blocks shipped,
        # raw K/V bytes moved, and blocks that dedup'd instead of
        # crossing the wire (a fat xfkb next to a zero dedup column
        # says the decode side's cache was cold for this prefix)
        xfers = [s for s in group if s["name"] == "kv.transfer"]
        annotated = ([a for a in admits if "xfer_blocks" in a["args"]]
                     + [x for x in xfers if "xfer_blocks" in x["args"]])
        if annotated:
            src = annotated[0]["args"]
            row["xfer_blocks"] = src["xfer_blocks"]
            row["xfer_bytes"] = src.get("xfer_bytes", 0)
            row["dedup_blocks"] = src.get("dedup_blocks", 0)
        # ledger-enabled engines (-cost_ledger) record one acct.request
        # span per finalized request: the tenant the usage was
        # attributed to and the folded cost units — the report then
        # says WHO each tail outlier belongs to and what it cost
        accts = [s for s in group if s["name"] == "acct.request"]
        if accts and "tenant" in accts[0]["args"]:
            row["tenant"] = accts[0]["args"]["tenant"]
            row["cost"] = accts[0]["args"].get("cost", 0.0)
        rows.append(row)
    return rows


def print_request_report(rows, top: int, sort: str,
                         slo_ms: float = 0.0) -> None:
    key = {"total": "total_ms", "queue": "queue_ms"}.get(sort, "total_ms")
    rows = sorted(rows, key=lambda r: r.get(key, 0.0), reverse=True)
    has_blocks = any("blocks" in r for r in rows)
    has_prefix = any("prefix_hit_blocks" in r for r in rows)
    has_tp = any("decode_tp" in r for r in rows)
    has_quant = any("kv_quant" in r for r in rows)
    has_sp = any("sp" in r for r in rows)
    has_preempt = any("preempted" in r for r in rows)
    has_xfer = any("xfer_blocks" in r for r in rows)
    has_tenant = any("tenant" in r for r in rows)
    has_keep = any(r.get("keep") for r in rows)
    # the node column ships as soon as the doc holds more than one
    # recording process (an obs-plane merged fleet trace); single-node
    # reports keep their classic layout
    has_node = len({r.get("node") for r in rows}) > 1
    breaches = (sum(r["total_ms"] > slo_ms for r in rows) if slo_ms > 0
                else 0)
    head = f"{len(rows)} request(s); slowest by {key}"
    if slo_ms > 0:
        head += (f"; {breaches} over the {slo_ms:g} ms SLO "
                 f"(flagged '!')")
    print(head + ":")
    hdr = (f"{'total':>9} {'queue':>8} {'admit':>8} {'prefill':>8} "
           f"{'exec':>8} {'decode':>8} {'iters':>6}")
    if has_node:
        hdr += f" {'node':>6}"
    if has_blocks:
        hdr += f" {'blocks':>7} {'pfree':>6}"
    if has_prefix:
        hdr += f" {'pfxhit':>7} {'saved':>6}"
    if has_tp:
        hdr += f" {'tp':>3}"
    if has_quant:
        hdr += f" {'quant':>6}"
    if has_sp:
        hdr += f" {'sp':>8}"
    if has_preempt:
        hdr += f" {'preempt':>8}"
    if has_xfer:
        hdr += f" {'xfblk':>6} {'xfkb':>8} {'dedup':>6}"
    if has_tenant:
        hdr += f" {'tenant':>10} {'cost':>9}"
    if has_keep:
        hdr += f" {'keep':>6}"
    print(hdr + "  trace_id [model]")
    for r in rows[:top]:
        flag = "!" if slo_ms > 0 and r["total_ms"] > slo_ms else " "
        line = (f"{r['total_ms']:8.3f}{flag} {r['queue_ms']:8.3f} "
                f"{r['admit_ms']:8.3f} {r.get('prefill_ms', 0.0):8.3f} "
                f"{r['exec_ms']:8.3f} "
                f"{r['decode_ms']:8.3f} {r['iters']:6d}")
        if has_node:
            line += f" {str(r.get('node', '-')):>6}"
        if has_blocks:
            line += (f" {str(r.get('blocks', '-')):>7} "
                     f"{str(r.get('pool_free', '-')):>6}")
        if has_prefix:
            line += (f" {str(r.get('prefix_hit_blocks', '-')):>7} "
                     f"{str(r.get('prefill_tokens_saved', '-')):>6}")
        if has_tp:
            line += f" {str(r.get('decode_tp', '-')):>3}"
        if has_quant:
            line += f" {str(r.get('kv_quant', '-')):>6}"
        if has_sp:
            line += f" {str(r.get('sp', '-')):>8}"
        if has_preempt:
            line += f" {str(r.get('preempted', '-')):>8}"
        if has_xfer:
            if "xfer_blocks" in r:
                line += (f" {r['xfer_blocks']:6d} "
                         f"{r.get('xfer_bytes', 0) / 1024.0:8.1f} "
                         f"{r.get('dedup_blocks', 0):6d}")
            else:
                line += f" {'-':>6} {'-':>8} {'-':>6}"
        if has_tenant:
            if "tenant" in r:
                line += (f" {str(r['tenant'])[:10]:>10} "
                         f"{r.get('cost', 0.0):9.3f}")
            else:
                line += f" {'-':>10} {'-':>9}"
        if has_keep:
            line += f" {r.get('keep') or '-':>6}"
        # non-request roots (snapshot.pin, table.add, bus.publish) label
        # themselves by span name instead of a model
        print(line + f"  {r['trace_id']} [{r['model'] or r['name']}]")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir", nargs="?", default=None,
                    help="xprof capture directory (*.trace.json.gz)")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--by", choices=["source", "op"], default="source")
    ap.add_argument("--host-trace", default=None,
                    help="multiverso_tpu.trace Chrome JSON: per-request "
                         "host breakdown")
    ap.add_argument("--sort", choices=["total", "queue"],
                    default="total", help="request-report sort column")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="flag requests whose total exceeds this latency "
                         "SLO and count the breaches (0 = off)")
    args = ap.parse_args(argv)

    if args.host_trace is None and args.trace_dir is None:
        ap.error("need an xprof TRACE_DIR, a --host-trace file, or both")

    events = load_events(args.trace_dir) if args.trace_dir else None
    if args.host_trace is not None:
        spans = load_host_spans(args.host_trace)
        rows = request_report(spans)
        print_request_report(rows, args.top, args.sort, args.slo_ms)
        if events is None:
            return 0
        print()
    dur, count, label = summarize(events, args.by)
    total = sum(dur.values())
    print(f"device time total: {total:.2f} ms "
          f"({sum(count.values())} op executions)")
    print(f"{'ms':>10} {'%':>6} {'n':>6}  {args.by}")
    for key, d in dur.most_common(args.top):
        tags = ", ".join(sorted(label[key])[:2])
        short = key if args.by == "op" else "/".join(key.split("/")[-2:])
        print(f"{d:10.2f} {d / total * 100:6.1f} {count[key]:6d}  "
              f"{short}  [{tags}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
