"""``lm_step_mfu``: ``work.train_flops_per_step`` x steps completed in
the traced window / (window x chips x bf16 peak), in percent. The
window, not the device's busy time, so idle counts against it;
recomputation is not counted."""

from benchmarks import work


def read(ctx):
    steps = ctx.counters.get("steps")
    if not steps or not ctx.counters.get("elapsed_s"):
        return None
    c, t = ctx.config, ctx.traffic
    flops = work.train_flops_per_step(c["n_embd"], c["n_layer"], c["n_inner"],
                                      c["vocab_size"], t["batch"], t["seq"])
    return 100.0 * flops * steps / (ctx.counters["elapsed_s"] * ctx.chips
                                    * ctx.peaks["bf16_flops"])
