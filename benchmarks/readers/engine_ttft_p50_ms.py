"""``engine_ttft_p50_ms``: the engine's own time-to-first-token median
(``eng.stats()``, a log-bucketed histogram on the host clock, reset at
the window's opening)."""


def read(ctx):
    value = ctx.counters.get("engine", {}).get("ttft_p50_ms")
    return value if value and value > 0 else None
