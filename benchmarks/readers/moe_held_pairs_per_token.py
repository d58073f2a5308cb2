"""``moe_held_pairs_per_token``: (token, held expert) pairs a token and
expert layer, over the tokens and layers of the window: the expert
layer's work that routing sends to THIS chip, from the program's routing
counter (``eng.stats()``). Design: picks x held / router outputs (8 x 16
/ 256 = 0.5 in ``dots-vlm1-ep16``); the held experts' FLOPs in every
``*_mfu`` of the cell follow it. A program without the counter: no
value."""


def read(ctx):
    return (ctx.counters.get("engine") or {}).get(
        "moe_held_pairs_per_token")
