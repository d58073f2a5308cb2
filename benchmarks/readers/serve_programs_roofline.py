"""``serve_programs_roofline``: the least time the chip could take for
what the engine's programs did in the traced window, over their summed
device time (the traffic file's ``engine_program``; the decode step and
the prefill chunk carry one name, so they are taken together), in
percent. Two parts, each by the bound that binds it:

* decode, HBM-bound (~2 FLOPs a parameter byte): an iteration reads the
  parameters once and K and V of the LIVE tokens its slots attend over
  (``work.decode_step_bytes``; the live tokens are those of the
  requests completed in the window), not the gathered ``slots x T``
  view;
* prefill, FLOP-bound at 512-token chunks: ``2 x matmul params`` a
  prompt token plus causal attention over the prompt
  (``work.lm_forward_flops_per_token``)."""

from benchmarks import work


def read(ctx):
    t, k = ctx.tracered, ctx.counters
    prog = (t or {}).get("programs", {}).get(ctx.traffic["engine_program"])
    if not prog or not prog["s"] or not k.get("completed") \
            or not k.get("iterations"):
        return None
    c = ctx.config
    dims = (c["n_embd"], c["n_layer"], c["n_inner"], c["vocab_size"])
    decode_bytes = k["iterations"] * work.decode_step_bytes(*dims, 0.0, 2) \
        + 2 * 2.0 * c["n_layer"] * c["n_embd"] * k["decode_context"]
    prefill_flops = 2.0 * work.lm_matmul_params(*dims) * k["prompt_tokens"] \
        + 4.0 * c["n_embd"] * c["n_layer"] * k["prefill_context"]
    least = decode_bytes / ctx.peaks["hbm_bytes_per_s"] \
        + prefill_flops / ctx.peaks["bf16_flops"]
    return 100.0 * least / prog["s"]
