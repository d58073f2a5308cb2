"""``engine_iter_ms``: seconds of the traced window over the engine
iterations in it (the flight recorder's ``total``, a count)."""


def read(ctx):
    iters = ctx.counters.get("iterations")
    if not iters or not ctx.counters.get("elapsed_s"):
        return None
    return 1e3 * ctx.counters["elapsed_s"] / iters
