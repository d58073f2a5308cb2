"""Driver ``serve_closed_cfg``: ``serve_closed``'s closed loop against a
model that the PROGRAM builds from the cell's configuration file
(``multiverso_tpu.models.from_config(config, seed)``), so that the driver
names no model. Everything else is ``serve_closed``'s own code, taken by
name: the loop, the window and its counts, the release, the check
against the plain reference and the controls.

Besides ``serve_closed``'s counts the window keeps the counters that the
model's programs accumulate on the device and ``eng.stats()`` hands out
(``moe_*`` for an expert model: picks and load), and the prompt tokens
the engine prefilled in the window (``prefill_tokens``: what the chunk
program ran, where ``prompt_tokens`` is of the requests that finished),
under ``info.engine``; a model without counters adds none.
"""

from __future__ import annotations

import time

from benchmarks import gen
from benchmarks.harness import load_module

_closed = load_module("drivers", "serve_closed")
ClosedLoop = _closed.ClosedLoop
release, check, controls = _closed.release, _closed.check, _closed.controls


def build(ctx, mv):
    from multiverso_tpu.models import from_config
    from multiverso_tpu.serving import InferenceServer
    from multiverso_tpu.serving.batcher import OverloadedError

    t = ctx.traffic
    lm = from_config(ctx.config, ctx.seed31)
    ctx.mark("model")
    srv = InferenceServer("bench")
    eng = srv.register_decoder(
        "lm", lm, slots=t["slots"], max_prompt=t["max_prompt"],
        max_new=t["max_new"], prefill_token_budget=t["prefill_token_budget"],
        max_queue=max(256, 2 * t["clients"]), **t.get("engine", {}))
    eng.warmup()
    ctx.mark("warm")
    requests = gen.serve_requests(
        ctx.seed, t["requests"], ctx.config["vocab_size"], t["prompt_min"],
        t["prompt_max"], t["new_min"], t["new_max"], t["length_cycle"])
    loop = ClosedLoop(lambda payload: srv.submit("lm", payload), requests,
                      t["clients"], shed=(OverloadedError,))
    loop.start()
    loop.pump(time.perf_counter() + t["ramp_s"],
              enough=lambda: len(loop.records) >= t["clients"])
    ctx.mark("ramped")
    return {"lm": lm, "srv": srv, "eng": eng, "loop": loop,
            "requests": requests}


def window(state, ctx, seconds: float) -> dict:
    out = _closed.window(state, ctx, seconds)
    # the model's own counters since the window opened (reset_stats):
    # read once, after the window, never a sync a step
    stats = state["eng"].stats()
    ctx.counters["engine"].update(
        {k: v for k, v in stats.items()
         if k.startswith("moe_") or k == "prefill_tokens"})
    return out
