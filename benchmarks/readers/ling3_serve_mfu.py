"""``ling3_serve_mfu``: the whole serving step's share of the chip's
bf16 peak, in percent: forward FLOPs of the requests completed in the
window (``work_ling3``: the matmul parameters a token multiplies in a
KDA or an MLA sublayer, a dense FFN or an expert layer outside its
routed experts, an expert's a (token, held expert) pair by the program's
routing counter, the KDA recurrence, MLA scores and values over the
context each token saw, expanded in prefill and latent in decode, and
the head) / (window x chips x peak). A program without the routing
counter: no value."""

from benchmarks import work_ling3 as wl


def read(ctx):
    k = ctx.counters
    pairs = (k.get("engine") or {}).get("moe_held_pairs_per_token")
    if pairs is None or not k.get("completed") or not k.get("elapsed_s"):
        return None
    c = ctx.config
    flops = wl.prefill_flops(c, k["prompt_tokens"], k["prefill_context"],
                             k["completed"], pairs) \
        + wl.decode_flops(c, k["out_tokens"], k["decode_context"], pairs)
    return 100.0 * flops / (k["elapsed_s"] * ctx.chips
                            * ctx.peaks["bf16_flops"])
