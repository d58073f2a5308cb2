"""``dsa_selected_share``: positions the decode steps' attention read
over those their indexer scored, from the program's own counters
(``eng.stats()``), each counted off what the step computed: the scores
it produced and the mask its attention ran under. How much of the
context the learned selection spares the attention: a design value of
the traffic, not a speed (about 0.37 in ``serve_glm5_ep16_closed16``: a
top-2,048 against contexts of 3,300-8,700); 1.0 is a selection that
never bites. A program without the counter: no value."""


def read(ctx):
    return (ctx.counters.get("engine") or {}).get("dsa_selected_share")
