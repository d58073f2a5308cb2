"""``kda_chunk_roofline``: the least time the chip could take for the
KDA recurrence of the traced prefill chunks (the larger of FLOPs / bf16
peak and bytes / HBM peak) over the summed device time of the chunk's
KDA ops (those whose HLO line matches the traffic file's
``kda_chunk_ops``), in percent. FLOPs: the recurrence as stated, a
prompt token and KDA layer (``work_ling3.kda_recurrence_flops``), over
the prompt tokens the engine prefilled (``eng.stats()``'s
``prefill_tokens``); bytes: a chunk reads and writes ONE slot's states
and tails, and a token's q, k, v, log-decay in and output out (float32).
Low by nature: the chunkwise form spends products the recurrence does
not state, and the chunk's KDA work is a few GFLOP of 5 TFLOP. Nothing to
read: no value."""

import re

from benchmarks import work_ling3 as wl


def read(ctx):
    t = ctx.tracered
    pattern = ctx.traffic.get("kda_chunk_ops")
    tokens = (ctx.counters.get("engine") or {}).get("prefill_tokens")
    prog = (t or {}).get("programs", {}).get(
        ctx.traffic.get("chunk_program", ""))
    if not t or not pattern or not tokens or not prog:
        return None
    pat = re.compile(pattern)
    op_s = sum(s for name, s in t["ops"].items() if pat.search(name))
    if op_s <= 0:
        return None
    c = ctx.config
    kda_layers = wl.layer_kinds(c)[0]
    flops = wl.kda_recurrence_flops(c) * kda_layers * tokens
    row = 5 * c["num_attention_heads"] * c["head_dim"] * 4
    nbytes = 2.0 * wl.slot_state_bytes(c) * prog["runs"] \
        + row * kda_layers * tokens
    least = max(flops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / op_s
