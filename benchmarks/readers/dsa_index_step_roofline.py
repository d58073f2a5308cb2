"""``dsa_index_step_roofline``: the least time the chip could take to
read the index keys the traced decode steps scored, over the summed
device time of the indexer's kernel calls (the ops whose HLO line
matches the traffic file's ``dsa_index_ops``), in percent. HBM-bound:
an index key (``work_glm5.index_key_bytes``) a position a layer's
indexer scored; the queries in and the scores out are under 2% of it
and left out, so the share reads a little low. Count and time cover the
SAME steps: the program's mean a step (``dsa_scored_positions`` over its
own ``dsa_step_runs``) times the step program's runs in the trace (the
traffic file's ``step_program``), never a window's total against a
trace's time. Nothing to read (no such op or program in the trace, no
counter): no value."""

import re

from benchmarks import work_glm5 as wg


def read(ctx):
    t = ctx.tracered
    pattern = ctx.traffic.get("dsa_index_ops")
    eng = ctx.counters.get("engine") or {}
    runs, scored = eng.get("dsa_step_runs"), eng.get("dsa_scored_positions")
    prog = (t or {}).get("programs", {}).get(
        ctx.traffic.get("step_program", ""))
    if not pattern or not prog or not runs or scored is None:
        return None
    pat = re.compile(pattern)
    op_s = sum(s for name, s in t["ops"].items() if pat.search(name))
    if op_s <= 0:
        return None
    least = wg.index_key_bytes(ctx.config) * scored / runs * prog["runs"] \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / op_s
