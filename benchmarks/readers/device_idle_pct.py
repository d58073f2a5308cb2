"""``device_idle_pct.*``: 100 x (1 - busy / window), where busy is the
union of the intervals in which an op ran on the chip, averaged over the
chips used, and the window is the host's ``bench.window`` span on the
profiler's clock. One reader; the name's suffix only says which
end-to-end metric the cell reports."""


def read(ctx):
    t = ctx.tracered
    if not t or not t["chips"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
