"""``ling3_decode_step_roofline``: the least time the chip could take
for the decode steps of the traced window, over the decode step
program's device time (the traffic file's ``step_program``), in
percent. HBM-bound: a step reads every matmul weight held here once
(``work_ling3.decode_weight_bytes``: the held experts' whatever the
routing), reads AND writes the recurrent state and conv tail of every
live slot (``slot_state_bytes``, twice a decoded token: the program's
own count of them, ``decode_tokens``), and reads the latent cache rows
of the live tokens its slots attend over (those of the requests
completed in the window). No such program in the trace, or no counter:
no value."""

from benchmarks import work_ling3 as wl


def read(ctx):
    t, k = ctx.tracered, ctx.counters
    prog = (t or {}).get("programs", {}).get(
        ctx.traffic.get("step_program", ""))
    decoded = wl.decode_tokens(ctx.config, k)
    if not prog or not prog["s"] or not k.get("completed") \
            or decoded is None:
        return None
    c = ctx.config
    least = (prog["runs"] * wl.decode_weight_bytes(c)
             + 2.0 * wl.slot_state_bytes(c) * decoded
             + wl.cache_row_bytes(c) * k["decode_context"]) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / prog["s"]
