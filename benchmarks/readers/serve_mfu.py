"""``serve_mfu``: the whole serving step's share of the chip's bf16
peak, in percent: forward FLOPs of the requests completed in the window
(``2 x matmul params`` a token prefilled or decoded, plus attention over
the context each token saw: 4 x context x d_model a layer) / (window x
chips x peak). Prefix-cache hits are not counted as work: this traffic
shares no prefix."""

from benchmarks import work


def read(ctx):
    k = ctx.counters
    if not k.get("completed") or not k.get("elapsed_s"):
        return None
    c = ctx.config
    tokens = k["prompt_tokens"] + k["out_tokens"]
    flops = 2.0 * work.lm_matmul_params(
        c["n_embd"], c["n_layer"], c["n_inner"], c["vocab_size"]) * tokens \
        + 4.0 * c["n_embd"] * c["n_layer"] \
        * (k["prefill_context"] + k["decode_context"])
    return 100.0 * flops / (k["elapsed_s"] * ctx.chips
                            * ctx.peaks["bf16_flops"])
