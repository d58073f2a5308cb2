"""``moe_home_group_share``: the share of (token, expert layer) pairs
whose kept groups (``topk_group`` of ``n_group``) include a group the
held experts lie in: the program's routing counter (``eng.stats()``).
It shows the group limit at work: design ``topk_group / n_group`` (0.5
in ``dots-vlm1-ep16``); 0 or 1 is a collapsed router, and a token
outside it can send this chip nothing, so ``moe_held_pairs_per_token``
is bounded by ``held experts x`` this. A program without the counter
(the parent; a router with no groups): no value."""


def read(ctx):
    return (ctx.counters.get("engine") or {}).get("moe_home_group_share")
