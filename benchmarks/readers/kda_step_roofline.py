"""``kda_step_roofline``: the least time the chip could take to move
what the KDA recurrence of the traced decode steps has to move, over the
summed device time of the step's KDA ops (those whose HLO line matches
the traffic file's ``kda_step_ops``), in percent. HBM-bound: every live
slot's float32 state and conv tail read once and written once a step
(``work_ling3.slot_state_bytes``, twice a decoded token: the program's
own count, ``decode_tokens``); a token's q, k, v and gates are under 1%
of its state and left out, so the share reads a little low. Nothing to
read (no such op in the trace, no counter): no value."""

import re

from benchmarks import work_ling3 as wl


def read(ctx):
    t = ctx.tracered
    pattern = ctx.traffic.get("kda_step_ops")
    decoded = wl.decode_tokens(ctx.config, ctx.counters)
    if not t or not pattern or decoded is None:
        return None
    pat = re.compile(pattern)
    op_s = sum(s for name, s in t["ops"].items() if pat.search(name))
    if op_s <= 0:
        return None
    least = 2.0 * wl.slot_state_bytes(ctx.config) * decoded \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / op_s
