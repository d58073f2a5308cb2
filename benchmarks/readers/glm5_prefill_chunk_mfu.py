"""``glm5_prefill_chunk_mfu``: the prefill chunks' share of the chip's
bf16 peak while the chunk program runs, in percent: forward FLOPs of the
prompt tokens the traced chunks held (``work_glm5.prefill_flops``: a
token's picks by the program's routing counter; the positions a query
scores taken as the mean over the prompts of the requests completed in
the window, ``prefill_context``, and those it attends the same under the
top-k, ``prefill_selected_context``; the head once a chunk) / (the chunk
program's device time, the traffic file's ``chunk_program``, x peak).
Tokens and time cover the SAME chunks: the program's prompt tokens a
chunk (``dsa_chunk_tokens`` over its own ``dsa_chunk_runs``) times the
chunk runs the trace holds. A chunk runs its attention as a dense
product under a mask over every visible position and its 512 rows
whatever the prompt holds, none of which is counted: the share reads
low by design. No such program in the trace, or no counter: no
value."""

from benchmarks import work_glm5 as wg


def read(ctx):
    t, k = ctx.tracered, ctx.counters
    prog = (t or {}).get("programs", {}).get(
        ctx.traffic.get("chunk_program", ""))
    eng = k.get("engine") or {}
    pairs, runs = eng.get("moe_held_pairs_per_token"), \
        eng.get("dsa_chunk_runs")
    if not prog or not prog["s"] or pairs is None or not runs \
            or not k.get("prompt_tokens") \
            or k.get("prefill_selected_context") is None:
        return None
    tokens = eng["dsa_chunk_tokens"] / runs * prog["runs"]
    per_token = tokens / k["prompt_tokens"]
    flops = wg.prefill_flops(ctx.config, tokens,
                             k["prefill_context"] * per_token,
                             k["prefill_selected_context"] * per_token,
                             prog["runs"], pairs)
    return 100.0 * flops / (prog["s"] * ctx.peaks["bf16_flops"])
