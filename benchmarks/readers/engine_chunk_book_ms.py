"""``engine_chunk_book_ms``: the host's milliseconds on the booking of
one retired prefill chunk, from the engine's ``engine.prefill_chunk.book``
phase (everything ``_retire_chunk`` does behind its sync: prefix
registration and, after a prompt's last chunk, the first token's argmax,
the histograms and the slot going live). Until PR 36 this was
``engine.iter``'s own time, under no phase. A program without the phase:
no value."""


def read(ctx):
    spans = (ctx.tracered or {}).get("spans") or {}
    book = spans.get("bench.engine.prefill_chunk.book")
    if not book or not book["n"]:
        return None
    return 1e3 * book["s"] / book["n"]
