"""Driver ``serve_closed_sparse``: ``serve_closed_routed``'s closed loop,
window and release, for a model whose attention reads only the
positions a learned indexer selects, with the selection itself held to
the reference beside the served tokens.

At a cell's size the served tokens hardly see a selection that adds
positions to the ``index_topk`` best or reorders them among their
neighbours: under the weight law the selected positions carry most of
what the dense attention reads, so a program that ignored its indexer
would serve nearly the same tokens (PERF.md section 7 (ab)). So the
comparison holds, beside ``serve_closed_routed``'s
``token_logit_gap_mean`` and ``short_answers``,

* ``selection_mismatch``: for ``check_queries`` decode queries of each
  checked request, evenly spaced over its served tokens, the positions
  each layer's attention reads as the PROGRAM chooses them (the model's
  ``attended_positions``: the served chunk's and step's own functions
  over the request's tokens) against those of the reference's one full
  forward pass at the same queries; pooled over queries and layers, the
  share of the union of the two sets that is not in both (1 - sum |A
  and B| / sum |A or B|). bfloat16 rounding moves the positions whose
  scores lie near the ``index_topk``-th; a selection that is ignored,
  approximated or scored wrong moves much of the set. A model without
  ``attended_positions``: no value, so no run is correct.

The window also keeps the ``dsa_*`` counters that such a model's
programs accumulate on the device and ``eng.stats()`` hands out (over
the decode steps, the positions scored and those the attention read,
and the step runs; over the prefill chunks, the chunk runs and the
tokens they held) under ``info.engine``, and the context the queries of
the requests completed in the window attend under the configuration's
``index_topk`` (``prefill_selected_context``,
``decode_selected_context``: ``serve_closed``'s ``prefill_context`` and
``decode_context`` with each term at most ``index_topk``). A model
without the counters adds none.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import load_module

_routed = load_module("drivers", "serve_closed_routed")
build = _routed.build


def selected_context(lo: int, hi: int, k: int) -> float:
    """``sum(min(x, k) for x in lo..hi)``: the positions that queries
    whose visible spans are ``lo..hi`` attend under a top-``k``
    selection."""
    if hi < lo:
        return 0.0
    return float(np.minimum(np.arange(lo, hi + 1), k).sum())


def window(state, ctx, seconds: float) -> dict:
    out = _routed.window(state, ctx, seconds)
    # read once, after the window, never a sync a step
    ctx.counters["engine"].update(
        {k: v for k, v in state["eng"].stats().items()
         if k.startswith("dsa_")})
    t_open, t_close = state["window"]
    done = [r for r in state["loop"].records
            if r[3] is not None and t_open <= r[2] <= t_close]
    requests, K = state["requests"], ctx.config["index_topk"]
    P = [len(requests[r[0] % len(requests)][0]) for r in done]
    N = [len(r[3]) for r in done]
    ctx.counters.update(
        prefill_selected_context=sum(selected_context(1, p, K) for p in P),
        decode_selected_context=sum(selected_context(p, p + n - 1, K)
                                    for p, n in zip(P, N)))
    return out


def decode_queries(prompt_len: int, length: int, n: int) -> np.ndarray:
    """``n`` positions evenly spaced over the decode steps' queries of a
    sequence of ``length`` tokens whose first ``prompt_len`` are its
    prompt: the served token at ``p`` is the query at ``p`` for
    ``prompt_len <= p <= length - 2``."""
    return np.linspace(prompt_len, max(length - 2, prompt_len),
                       n).round().astype(np.int32)


def release(state, ctx) -> dict:
    """``serve_closed``'s release, then the program's selections at the
    sample's decode queries, taken before the model goes."""
    lm = state["lm"]
    got = _routed.release(state, ctx)
    t = ctx.traffic
    got["queries"] = [decode_queries(p, len(s), int(t["check_queries"]))
                      for s, p in zip(got["sequences"], got["prompt_lens"])]
    if hasattr(lm, "attended_positions"):
        got["attended"] = [lm.attended_positions(
            s, q, block_size=int(t["engine"]["kv_block_size"]),
            cache_len=int(t["max_prompt"]) + int(t["max_new"]),
            chunk=int(t["prefill_token_budget"]))
            for s, q in zip(got["sequences"], got["queries"])]
    return got


def selection_mismatch(chosen: list, reference: list):
    """``1 - sum |A and B| / sum |A or B|`` over every sequence, layer and
    query: ``chosen`` per sequence ``[L, Q, K]`` positions (-1: none) or
    ``[L, Q, T]`` bool, ``reference`` per sequence ``[L, Q, T]`` bool (a
    position past ``T`` counts as none). None where nothing was
    chosen."""
    if chosen is None or not reference:
        return None
    both = either = 0
    for a, b in zip(chosen, reference):
        a = np.asarray(a)
        if a.dtype != bool:
            mask = np.zeros(b.shape[:2] + (b.shape[2] + 1,), bool)
            T = b.shape[2]
            np.put_along_axis(mask, np.where((a < 0) | (a >= T), T, a),
                              True, axis=-1)
            a = mask[..., :-1]
        both += int((a & b).sum())
        either += int((a | b).sum())
    return 1.0 - both / either if either else None


def compare(tables: list, got: dict, limits: dict, mismatch) -> list:
    return _routed.compare(tables, got, limits) + [{
        "name": "selection_mismatch", "value": mismatch,
        "limit": limits["selection_mismatch"]}]


def check(got: dict, ctx) -> list:
    ref = load_module("reference", ctx.config_name)
    args = (ctx.config, ctx.seed31, got["sequences"])
    exact = ref.padded_logits(*args, queries=got["queries"])
    tables = ref.token_gap_tables(*args, got["prompt_lens"], exact=exact)
    gaps = np.concatenate(tables) if tables else np.zeros(0, np.float32)
    mismatch = selection_mismatch(got.get("attended"), exact[1].selected)
    ctx.counters["checked"] = {
        "requests": len(tables), "served_tokens": int(gaps.size),
        "gap_max": [float(t.max()) if t.size else 0.0 for t in tables],
        "moved_share": float((gaps > 0).mean()) if gaps.size else None,
        "queries": int(sum(len(q) for q in got["queries"]))}
    return compare(tables, got, ctx.traffic["limits"], mismatch)


def controls(got: dict, ctx) -> dict:
    """``serve_closed_routed``'s controls, each read on both rows: at the
    same prompts, served tokens and queries, the gap (in the exact
    reference's logits) of the token that the reference puts first, and
    the mismatch of the positions it selects, with 8-bit-float matmuls,
    and with each planted fault under bfloat16 matmuls."""
    ref = load_module("reference", ctx.config_name)
    args = (ctx.config, ctx.seed31, got["sequences"])
    exact = ref.padded_logits(*args, queries=got["queries"])
    out = {"control_float8_e4m3fn": ("float8_e4m3fn", "")}
    out.update({f"fault_{f}": ("bfloat16", f) for f in ref.FAULTS})
    rows = {}
    for name, (compute, fault) in out.items():
        other = ref.padded_logits(*args, compute, fault,
                                  queries=got["queries"])
        tables = ref.token_gap_tables(*args, got["prompt_lens"], compute,
                                      fault, exact=exact, other=other)
        rows[name] = compare(tables, got, ctx.traffic["limits"],
                             selection_mismatch(other[1].selected,
                                                exact[1].selected))
    return rows
