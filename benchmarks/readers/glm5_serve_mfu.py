"""``glm5_serve_mfu``: the whole serving step's share of the chip's bf16
peak, in percent: forward FLOPs of the requests completed in the window
(``work_glm5``: the matmul parameters a token multiplies in a dense and
in an expert layer outside its routed experts, an expert's a (token,
held expert) pair by the program's routing counter, the indexer's
products over every position a token saw, MLA's scores and values over
the positions it attended, expanded in prefill and latent in decode, and
the head) / (window x chips x peak). A program without the routing
counter: no value."""

from benchmarks import work_glm5 as wg


def read(ctx):
    k = ctx.counters
    pairs = (k.get("engine") or {}).get("moe_held_pairs_per_token")
    if pairs is None or not k.get("completed") or not k.get("elapsed_s") \
            or k.get("decode_selected_context") is None:
        return None
    c = ctx.config
    flops = wg.prefill_flops(c, k["prompt_tokens"], k["prefill_context"],
                             k["prefill_selected_context"], k["completed"],
                             pairs) \
        + wg.decode_flops(c, k["out_tokens"], k["decode_context"],
                          k["decode_selected_context"], pairs)
    return 100.0 * flops / (k["elapsed_s"] * ctx.chips
                            * ctx.peaks["bf16_flops"])
