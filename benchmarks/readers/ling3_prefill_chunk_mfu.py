"""``ling3_prefill_chunk_mfu``: the prefill chunks' share of the chip's
bf16 peak while the chunk program runs, in percent: forward FLOPs of the
prompt tokens the engine prefilled in the traced window
(``eng.stats()``'s ``prefill_tokens``; ``work_ling3.prefill_flops``; each
token's visible span in the latent layer taken as the mean over the
prompts of the requests completed in the window, the head once a chunk)
/ (the chunk program's device time, the traffic file's
``chunk_program``, x peak). It counts the rows that hold a prompt's
tokens and the held experts a token picked, so a chunk with every held
expert run over every row reads low by design (picked work is 1/64 of
the chunk's expert FLOPs). No such program in the trace, or no routing
counter: no value."""

from benchmarks import work_ling3 as wl


def read(ctx):
    t, k = ctx.tracered, ctx.counters
    prog = (t or {}).get("programs", {}).get(
        ctx.traffic.get("chunk_program", ""))
    eng = k.get("engine") or {}
    pairs, tokens = eng.get("moe_held_pairs_per_token"), \
        eng.get("prefill_tokens")
    if not prog or not prog["s"] or pairs is None or not tokens \
            or not k.get("prompt_tokens"):
        return None
    span = k["prefill_context"] / k["prompt_tokens"]
    flops = wl.prefill_flops(ctx.config, tokens, span * tokens,
                             prog["runs"], pairs)
    return 100.0 * flops / (prog["s"] * ctx.peaks["bf16_flops"])
