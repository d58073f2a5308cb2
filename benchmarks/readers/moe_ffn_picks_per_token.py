"""``moe_ffn_picks_per_token``: of a token's ``moe_topk`` picks in an
expert layer, how many fell on FFN experts (held here or not) and not on
identity experts, averaged over the tokens and layers of the window:
the program's routing counter (``eng.stats()``). With 512 FFN and 256
identity outputs and 12 picks the design is 8.0; the held experts' share
of the work follows it. A program without the counter: no value."""


def read(ctx):
    return (ctx.counters.get("engine") or {}).get("moe_ffn_picks_per_token")
