"""``engine_prefill_chunk_ms``: the engine loop's
``engine.prefill_chunk`` phase (``DecodeEngine._prefill_one_chunk``
whole: argument build, dispatch, the sync on the K/V pool, first-token
booking on a prompt's last chunk) per chunk, from the host events the
program writes into the profiler's trace. One chunk an iteration is the
admission lane's rate, so it moves the tail through TTFT. A program
without the phase: no value."""


def read(ctx):
    rec = ((ctx.tracered or {}).get("spans") or {}).get(
        "bench.engine.prefill_chunk")
    if not rec or not rec["n"]:
        return None
    return 1e3 * rec["s"] / rec["n"]
