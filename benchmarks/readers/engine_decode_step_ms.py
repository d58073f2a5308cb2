"""``engine_decode_step_ms``: the engine loop's ``engine.step`` phase
(``DecodeEngine._step`` whole: drafts, block growth, dispatch, the sync
on the step's tokens, the per-slot booking) per step, from the host
events the program writes into the profiler's trace
(``multiverso_tpu.trace.phase``). A program without the phase: no
value."""


def read(ctx):
    rec = ((ctx.tracered or {}).get("spans") or {}).get(
        "bench.engine.step")
    if not rec or not rec["n"]:
        return None
    return 1e3 * rec["s"] / rec["n"]
