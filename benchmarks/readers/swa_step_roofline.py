"""``swa_step_roofline``: the least time the chip could take to read
what the sliding-window layers of the traced decode steps attend over,
over the summed device time of the step's sliding-layer attention ops
(those whose HLO line matches the traffic file's ``swa_step_ops``: the
paged attention kernel under the name the sliding layers' calls carry),
in percent. HBM-bound: each live slot's ``min(pos + 1, sliding_window)``
ring rows a sliding layer (the program's own count,
``swa_sliding_positions``), ``work_mellum2.cache_row_bytes`` a row; the
queries in and outputs out are under 2% of it and left out, so the share
reads a little low. Nothing to read (no such op in the trace, no
counter): no value."""

import re

from benchmarks import work_mellum2 as wm


def read(ctx):
    t = ctx.tracered
    pattern = ctx.traffic.get("swa_step_ops")
    sliding = (ctx.counters.get("engine") or {}).get("swa_sliding_positions")
    if not t or not pattern or sliding is None:
        return None
    pat = re.compile(pattern)
    op_s = sum(s for name, s in t["ops"].items() if pat.search(name))
    if op_s <= 0:
        return None
    c = ctx.config
    least = wm.cache_row_bytes(c) * wm.layer_kinds(c)[0] * sliding \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / op_s
