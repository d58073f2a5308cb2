"""Work counts of a ``mellum`` model (configuration ``mellum2-12b-l8``),
from the configuration's sizes: what ``mellum2_serve_mfu``,
``mellum2_decode_step_roofline``, ``mellum2_prefill_chunk_mfu`` and
``swa_step_roofline`` divide by a peak of ``work.PEAKS`` and a time.
Like ``work.py`` they are the yardstick: the least work the algorithm
needs, whatever the program does.

A "token" here is one row through the ``num_hidden_layers`` layers held
on this chip, each a grouped-query attention (full or sliding, as
``layer_types`` says) and an expert layer: the router and the token's
``num_experts_per_tok`` picks (``pairs_per_token``, the program's
counter; every expert runs over every row of a decode step, which is
not counted).

What a token leaves in a layer's cache is one ``[V | K]`` row of ``2 KV
head_dim`` values. A full layer's query attends over every earlier
position; a sliding layer's over at most ``sliding_window`` of them, and
a slot's sliding layers hold that many rows whatever its context.
"""

from __future__ import annotations

import numpy as np


def layer_kinds(c: dict) -> tuple:
    """``(sliding layers, full layers)`` held here."""
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    full = sum(k == "full_attention" for k in kinds)
    return len(kinds) - full, full


def attention_params(c: dict) -> int:
    """Matmul parameters a token multiplies in one attention sublayer:
    W_q, W_k, W_v and W_o."""
    D, H, KV, dh = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"], c["head_dim"]
    return 2 * D * H * dh + 2 * D * KV * dh


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["num_experts"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def cache_row_bytes(c: dict, itemsize: int = 2) -> int:
    """Bytes of one token's cache row in ONE layer: ``[V | K]`` (2,048 B
    at the published sizes)."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * itemsize


def decode_weight_bytes(c: dict, itemsize: int = 2) -> float:
    """HBM bytes one decode step has to read whatever the routing: every
    matmul weight once (attention, all the experts, the head), the
    router in float32. The embedding is a gather."""
    return (c["num_hidden_layers"]
            * ((attention_params(c) + c["num_experts"] * expert_params(c))
               * itemsize + router_params(c) * 4)
            + head_params(c) * itemsize)


def attention_flops_per_pair(c: dict) -> float:
    """FLOPs of one (query token, visible token) pair in ONE layer, all
    heads: scores and values over ``head_dim`` a head."""
    return 4.0 * c["num_attention_heads"] * c["head_dim"]


def token_flops(c: dict, pairs_per_token: float) -> float:
    """Forward FLOPs of one token through the layers, attention over the
    context and the head left out: 2 x the matmul parameters it
    multiplies (an expert's a (token, expert) pair)."""
    return 2.0 * c["num_hidden_layers"] * (
        attention_params(c) + router_params(c)
        + pairs_per_token * expert_params(c))


def context_flops(c: dict, full_context: float,
                  window_context: float) -> float:
    """Attention over the context: ``full_context`` (query, visible)
    pairs a full layer, ``window_context`` a sliding layer."""
    sliding, full = layer_kinds(c)
    return attention_flops_per_pair(c) * (full * full_context
                                          + sliding * window_context)


def prefill_flops(c: dict, prompt_tokens: float, full_context: float,
                  window_context: float, requests: float,
                  pairs_per_token: float) -> float:
    """Prefill of ``prompt_tokens`` tokens in all, ``requests`` prompts
    (the head runs on a prompt's last token only)."""
    return (token_flops(c, pairs_per_token) * prompt_tokens
            + context_flops(c, full_context, window_context)
            + 2.0 * head_params(c) * requests)


def decode_flops(c: dict, out_tokens: float, full_context: float,
                 window_context: float, pairs_per_token: float) -> float:
    return ((token_flops(c, pairs_per_token) + 2.0 * head_params(c))
            * out_tokens + context_flops(c, full_context, window_context))


def window_context(lo: int, hi: int, window: int) -> float:
    """``sum(min(x, window) for x in lo..hi)``: the positions a sliding
    layer's queries see where a full layer's see ``x``."""
    if hi < lo:
        return 0.0
    return float(np.minimum(np.arange(lo, hi + 1), window).sum())


def step_bytes(c: dict, runs: float, full_positions: float,
               sliding_positions: float) -> float:
    """HBM bytes ``runs`` decode steps have to read: the weights once a
    step, and the cache rows their live slots attend over, from the
    program's counters: ``full_positions`` a full layer,
    ``sliding_positions`` a sliding layer (``min(pos + 1, window)`` a
    live slot and step)."""
    sliding, full = layer_kinds(c)
    return runs * decode_weight_bytes(c) + cache_row_bytes(c) * (
        full * full_positions + sliding * sliding_positions)
