"""``engine_unphased_ms_per_iter``: the loop thread's time that no leaf
phase names, per iteration: the traced window less ``engine.wait`` and
less the eight leaf phases of a pass (the children of ``engine.iter``),
over the iterations. It holds a pass's own time between its phases and
the loop gap between two passes together; what the device does
meanwhile is not its business. A leaf phase that the trace lacks counts
as 0 s, so a program from before ``engine.prefill_chunk.book`` (PR 36)
reads its chunk booking here. A profiler session cuts the engine's
first and last pass, so the value is off by up to one pass over the
iterations. A program without the phases: no value."""

LEAVES = ("step", "admit", "prefill_chunk", "step.sync", "step.book",
          "prefill_chunk.sync", "prefill_chunk.book", "record")


def read(ctx):
    reduced = ctx.tracered or {}
    spans = reduced.get("spans") or {}
    it = spans.get("bench.engine.iter")
    if not it or not it["n"] or not reduced.get("window_s"):
        return None
    named = sum(spans.get("bench.engine." + leaf, {"s": 0.0})["s"]
                for leaf in LEAVES + ("wait",))
    return 1e3 * (reduced["window_s"] - named) / it["n"]
