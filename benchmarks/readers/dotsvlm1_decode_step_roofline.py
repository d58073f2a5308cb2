"""``dotsvlm1_decode_step_roofline``: the least time the chip could take
for the decode steps of the traced window, over the decode step
program's device time (the traffic file's ``step_program``), in
percent. HBM-bound: a step reads every matmul weight held here once
(``work_dsv3.decode_weight_bytes``: the held experts' whatever the
routing) and the cache rows of the live tokens its slots attend over,
``(rkv + dr)`` values a token a layer, not the gathered ``slots x T``
view; the live tokens are those of the requests completed in the
window. No such program in the trace: no value."""

from benchmarks import work_dsv3 as wd


def read(ctx):
    t, k = ctx.tracered, ctx.counters
    prog = (t or {}).get("programs", {}).get(
        ctx.traffic.get("step_program", ""))
    if not prog or not prog["s"] or not k.get("completed"):
        return None
    c = ctx.config
    least = (prog["runs"] * wd.decode_weight_bytes(c)
             + wd.cache_row_bytes(c) * k["decode_context"]) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / prog["s"]
