"""``kda_mean_log_decay``: the mean of the KDA gate's ``log a`` over the
tokens, KDA layers, heads and key channels of the window, from the
program's own counter (``eng.stats()``): how fast the recurrent state
forgets. A design value of the configuration's weight law, not a speed
(about -0.05 in ``ling3-flash-ep4``: a memory of tens to hundreds of
tokens); 0 (nothing ever forgotten) or ``kda_lower_bound`` (a one-token
memory) is a collapsed gate. A program without the counter: no value."""


def read(ctx):
    return (ctx.counters.get("engine") or {}).get("kda_mean_log_decay")
