"""``w2v_step_mfu``: the whole step's share of the chips' bf16 peak, in
percent: model FLOPs per pair (``work.w2v_flops_per_pair``) x pairs
trained in the traced window / (window x chips x peak). The window, not
the busy time, so idle counts against it. The step is gather/scatter
bound, so this reads a small fraction of a percent; it is the share that
still bounds a claim when a kernel leaves the path."""

from benchmarks import work


def read(ctx):
    pairs = ctx.counters.get("pairs")
    if not pairs or not ctx.counters.get("elapsed_s"):
        return None
    flops = work.w2v_flops_per_pair(ctx.config["embedding_size"],
                                    ctx.config["negative"]) * pairs
    return 100.0 * flops / (ctx.counters["elapsed_s"] * ctx.chips
                            * ctx.peaks["bf16_flops"])
