"""``moe_held_load_max_over_mean``: the busiest held expert's (token,
expert) pairs over the mean of the held experts', summed over the layers
and the window: the program's routing counter (``eng.stats()``). 1.0 is
an even load; a large value means the tokens route alike and the held
experts' work is a draw of the seed (ledger, PR 27: 10.3). A program
without the counter: no value."""


def read(ctx):
    return (ctx.counters.get("engine") or {}).get(
        "moe_held_load_max_over_mean")
