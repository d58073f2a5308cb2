"""Driver ``serve_closed_hybrid``: ``serve_closed_routed`` whole (build,
window, release, the mean-gap comparison, the controls), for a model
that keeps a recurrent state beside its blocks: the window also keeps
the ``kda_*`` counters that such a model's programs accumulate on the
device and ``eng.stats()`` hands out (``serve_closed_cfg.window`` keeps
``moe_*`` only), under ``info.engine``. A model without them adds none.
"""

from __future__ import annotations

from benchmarks.harness import load_module

_routed = load_module("drivers", "serve_closed_routed")
build, release = _routed.build, _routed.release
compare, check, controls = _routed.compare, _routed.check, _routed.controls


def window(state, ctx, seconds: float) -> dict:
    out = _routed.window(state, ctx, seconds)
    # read once, after the window, never a sync a step
    ctx.counters["engine"].update(
        {k: v for k, v in state["eng"].stats().items()
         if k.startswith("kda_")})
    return out
