"""``engine_program_retraces``: compilations of the engine's two
programs beyond the one each that warm-up makes, as the window's end
finds them (``eng.stats()``: ``step_traces`` - 1 + ``prefill_traces``
- 1). Anything above 0 is a program that compiled again under traffic:
seconds of a window lost to it. No engine counters: no value (a true 0
is reported)."""


def read(ctx):
    engine = ctx.counters.get("engine") or {}
    if "step_traces" not in engine or "prefill_traces" not in engine:
        return None
    return float(max(0, engine["step_traces"] - 1)
                 + max(0, engine["prefill_traces"] - 1))
