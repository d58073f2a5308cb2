"""``mellum2_prefill_chunk_mfu``: the prefill chunks' share of the
chip's bf16 peak while the chunk program runs, in percent: forward FLOPs
of the prompt tokens the engine prefilled in the traced window
(``eng.stats()``'s ``prefill_tokens``; ``work_mellum2.prefill_flops``:
a token's picks by the program's routing counter; the context a full
layer's query sees taken as the mean over the prompts of the requests
completed in the window, a sliding layer's the same capped at the
window, ``prefill_window_context``; the head once a chunk) / (the chunk
program's device time, the traffic file's ``chunk_program``, x peak).
No such program in the trace, or no counter: no value."""

from benchmarks import work_mellum2 as wm


def read(ctx):
    t, k = ctx.tracered, ctx.counters
    prog = (t or {}).get("programs", {}).get(
        ctx.traffic.get("chunk_program", ""))
    eng = k.get("engine") or {}
    pairs, tokens = eng.get("moe_held_pairs_per_token"), \
        eng.get("prefill_tokens")
    if not prog or not prog["s"] or pairs is None or not tokens \
            or not k.get("prompt_tokens") \
            or k.get("prefill_window_context") is None:
        return None
    per_token = tokens / k["prompt_tokens"]
    flops = wm.prefill_flops(ctx.config, tokens,
                             k["prefill_context"] * per_token,
                             k["prefill_window_context"] * per_token,
                             prog["runs"], pairs)
    return 100.0 * flops / (prog["s"] * ctx.peaks["bf16_flops"])
