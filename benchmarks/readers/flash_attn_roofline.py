"""``flash_attn_roofline``: the least time the chip could take for
the causal attention of the traced steps (forward and backward, every
layer; the larger of FLOPs / bf16 peak and bytes / HBM peak, from
``work.causal_attention_work``) over the summed device time of the flash
kernels' ops (those whose HLO line matches the traffic file's
``flash_ops``), in percent. At head size 64 and seq 1,024 the FLOP bound
binds (arithmetic intensity ~seq/4 FLOPs a byte forward). Nothing to
read (no kernel in the trace): no value."""

import re

from benchmarks import work


def read(ctx):
    t = ctx.tracered
    prog = (t or {}).get("programs", {}).get(ctx.traffic["step_program"])
    if not prog or not prog["runs"]:
        return None
    pat = re.compile(ctx.traffic["flash_ops"])
    kernel_s = sum(s for name, s in t["ops"].items() if pat.search(name))
    if kernel_s <= 0:
        return None
    c, tr = ctx.config, ctx.traffic
    least = 0.0
    for backward in (False, True):
        flops, nbytes = work.causal_attention_work(
            tr["batch"], c["n_head"], tr["seq"], c["n_embd"] // c["n_head"],
            2, backward)
        least += max(flops / ctx.peaks["bf16_flops"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * c["n_layer"] * prog["runs"] / kernel_s
