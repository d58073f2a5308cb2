"""Driver ``serve_closed_window``: ``serve_closed_routed`` whole (build,
window, release, the mean-gap comparison, the controls), for a model
whose sliding-window layers keep a ring a slot beside the full layers'
blocks: the window also keeps the ``swa_*`` counters that such a model's
programs accumulate on the device and ``eng.stats()`` hands out (the
positions a sliding and a full layer read in the steps, and the tokens)
under ``info.engine``, and the context a sliding layer attends over in
the requests completed in the window, capped at the configuration's
``sliding_window`` (``prefill_window_context``,
``decode_window_context``: ``serve_closed``'s ``prefill_context`` and
``decode_context`` with each term at most the window). A model without
the counters adds none.
"""

from __future__ import annotations

from benchmarks import work_mellum2
from benchmarks.harness import load_module

_routed = load_module("drivers", "serve_closed_routed")
build, release = _routed.build, _routed.release
compare, check, controls = _routed.compare, _routed.check, _routed.controls


def window(state, ctx, seconds: float) -> dict:
    out = _routed.window(state, ctx, seconds)
    # read once, after the window, never a sync a step
    ctx.counters["engine"].update(
        {k: v for k, v in state["eng"].stats().items()
         if k.startswith("swa_")})
    t_open, t_close = state["window"]
    done = [r for r in state["loop"].records
            if r[3] is not None and t_open <= r[2] <= t_close]
    requests, W = state["requests"], ctx.config["sliding_window"]
    P = [len(requests[r[0] % len(requests)][0]) for r in done]
    N = [len(r[3]) for r in done]
    ctx.counters.update(
        prefill_window_context=sum(
            work_mellum2.window_context(1, p, W) for p in P),
        decode_window_context=sum(
            work_mellum2.window_context(p, p + n - 1, W)
            for p, n in zip(P, N)))
    return out
