"""``mellum2_decode_step_roofline``: the least time the chip could take
for the decode steps of the traced window, over the decode step
program's device time (the traffic file's ``step_program``), in
percent. HBM-bound: a step reads every matmul weight held here once
(``work_mellum2.decode_weight_bytes``: all the experts' whatever the
routing), and the cache rows its live slots attend over: ``pos + 1``
rows a full layer and ``min(pos + 1, sliding_window)`` a sliding layer,
the program's own counts of them (``swa_full_positions``,
``swa_sliding_positions``; ``work_mellum2.step_bytes``). No such program
in the trace, or no counter: no value."""

from benchmarks import work_mellum2 as wm


def read(ctx):
    t = ctx.tracered
    eng = ctx.counters.get("engine") or {}
    prog = (t or {}).get("programs", {}).get(
        ctx.traffic.get("step_program", ""))
    full, sliding = eng.get("swa_full_positions"), \
        eng.get("swa_sliding_positions")
    if not prog or not prog["s"] or full is None or sliding is None:
        return None
    least = wm.step_bytes(ctx.config, prog["runs"], full, sliding) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / prog["s"]
