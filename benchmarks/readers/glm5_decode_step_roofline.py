"""``glm5_decode_step_roofline``: the least time the chip could take
for the decode steps of the traced window, over the decode step
program's device time (the traffic file's ``step_program``), in
percent. HBM-bound: a step reads every matmul weight held here once
(``work_glm5.decode_weight_bytes``: the held experts' whatever the
routing, the embedding a gather), an index key a position its indexer
scored and a latent row a position it attended
(``work_glm5.step_bytes``). Both counts and the time cover the SAME
steps: the program counts positions and its own step runs over the
engine's window (``dsa_scored_positions``, ``dsa_attended_positions``,
``dsa_step_runs``), and their means a step are multiplied by the step
runs the trace holds, never a window's total against a trace's time.
No such program in the trace, or no counter: no value."""

from benchmarks import work_glm5 as wg


def read(ctx):
    t = ctx.tracered
    eng = ctx.counters.get("engine") or {}
    prog = (t or {}).get("programs", {}).get(
        ctx.traffic.get("step_program", ""))
    runs = eng.get("dsa_step_runs")
    if not prog or not prog["s"] or not runs \
            or eng.get("dsa_scored_positions") is None:
        return None
    least = wg.step_bytes(ctx.config, prog["runs"],
                          eng["dsa_scored_positions"] / runs,
                          eng["dsa_attended_positions"] / runs) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / prog["s"]
