"""``swa_read_share``: positions a sliding-window layer read in the
decode steps of the window over those a full layer read, from the
program's own counters (``eng.stats()``): how much of the context the
window spares a sliding layer. A design value of the traffic, not a
speed (about 0.45 in ``serve_mellum2_closed128``: a 1,024-position
window against a mean context near 2,250); 1.0 is a window that never
bites (or is ignored). A program without the counter: no value."""


def read(ctx):
    return (ctx.counters.get("engine") or {}).get("swa_read_share")
