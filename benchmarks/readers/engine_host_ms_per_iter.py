"""``engine_host_ms_per_iter``: what of an engine iteration is the
host's: the ``engine.iter`` phases less the two in which the loop
thread waits for the device (``engine.step.sync``,
``engine.prefill_chunk.sync``), per iteration. It bounds the loop's host
work, which is serial with the device; it is NOT the device's idle: the
launch (the head of ``engine.step`` before its sync) is in it, but the
time a dispatched program takes to start falls inside the syncs and is
subtracted with them (on the v5e most of the idle, PERF.md section 5).
A profiler session cuts its first and last iteration: a child of an
iteration that began before the session is in the trace without its
parent, so up to one sync at each end is subtracted from an iteration
that is not in the sum. A program without the phases: no value."""


def read(ctx):
    spans = (ctx.tracered or {}).get("spans") or {}
    it = spans.get("bench.engine.iter")
    if not it or not it["n"]:
        return None
    syncs = sum(spans.get(name, {"s": 0.0})["s"] for name in (
        "bench.engine.step.sync", "bench.engine.prefill_chunk.sync"))
    return 1e3 * (it["s"] - syncs) / it["n"]
