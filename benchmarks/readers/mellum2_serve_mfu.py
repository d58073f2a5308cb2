"""``mellum2_serve_mfu``: the whole serving step's share of the chip's
bf16 peak, in percent: forward FLOPs of the requests completed in the
window (``work_mellum2``: the matmul parameters a token multiplies, an
expert's a (token, expert) pair by the program's routing counter,
attention scores and values over the context each token saw, a sliding
layer's capped at the window, and the head) / (window x chips x peak).
A program without the routing counter: no value."""

from benchmarks import work_mellum2 as wm


def read(ctx):
    k = ctx.counters
    pairs = (k.get("engine") or {}).get("moe_held_pairs_per_token")
    if pairs is None or not k.get("completed") or not k.get("elapsed_s") \
            or k.get("decode_window_context") is None:
        return None
    c = ctx.config
    flops = wm.prefill_flops(c, k["prompt_tokens"], k["prefill_context"],
                             k["prefill_window_context"], k["completed"],
                             pairs) \
        + wm.decode_flops(c, k["out_tokens"], k["decode_context"],
                          k["decode_window_context"], pairs)
    return 100.0 * flops / (k["elapsed_s"] * ctx.chips
                            * ctx.peaks["bf16_flops"])
