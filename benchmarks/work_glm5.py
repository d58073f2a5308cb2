"""Work counts of a ``glm_moe_dsa`` share (configuration ``glm5-ep16``),
from the configuration's sizes: what ``glm5_serve_mfu``,
``glm5_decode_step_roofline``, ``glm5_prefill_chunk_mfu`` and
``dsa_index_step_roofline`` divide by a peak of ``work.PEAKS`` and a
time. Like ``work.py`` they are the yardstick: the least work the
algorithm needs, whatever the program does.

A "token" here is one row through the ``num_hidden_layers`` layers held
on this chip, each an MLA sublayer with its indexer, then a dense SwiGLU
FFN in the ``first_k_dense_replace`` leading layers and, in the expert
layers, the router, the shared expert and the token's picks among the
HELD routed experts (``held_pairs_per_token`` a layer, the program's
counter: the held experts run over every row of a decode step, which is
not counted).

In each layer a query at position ``p`` SCORES its ``p + 1`` visible
positions with the indexer (``Hi x dI`` multiply-adds a position) and
ATTENDS to ``min(index_topk, p + 1)`` of them. A prefill chunk runs the
attention as a dense product under a mask, over every visible position:
that extra work is not counted, so a chunk's share of the peak reads low
by design.
"""

from __future__ import annotations

# MLA's matmul parameters, a (query, token) pair in both attention forms
# and the head count: the same for every model with these keys
from benchmarks.work_longcat import (attention_flops_per_pair, head_params,
                                     mla_params)


def layers(c: dict) -> tuple:
    """``(dense layers, expert layers)`` held here."""
    dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    return dense, c["num_hidden_layers"] - dense


def router_outputs(c: dict) -> int:
    return int(c.get("published", {}).get("n_routed_experts",
                                          c["n_routed_experts"]))


def indexer_params(c: dict) -> int:
    """Matmul parameters a token multiplies in one layer's indexer:
    ``W_Iq`` (from the query latent), ``W_Ik`` and ``W_Iw``."""
    D, Hi, dI = c["hidden_size"], c["index_n_heads"], c["index_head_dim"]
    return c["q_lora_rank"] * Hi * dI + D * dI + D * Hi


def attention_params(c: dict) -> int:
    """MLA and its indexer."""
    return mla_params(c) + indexer_params(c)


def expert_params(c: dict) -> int:
    """One routed expert (the shared expert is ``n_shared_experts`` of
    them wide)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_layer_params(c: dict) -> int:
    return attention_params(c) + 3 * c["hidden_size"] * c["intermediate_size"]


def expert_layer_params_outside_routed(c: dict) -> int:
    """Attention, the router and the shared expert: what every token of
    an expert layer multiplies whatever it picks."""
    return (attention_params(c) + c["hidden_size"] * router_outputs(c)
            + c["n_shared_experts"] * expert_params(c))


def index_key_bytes(c: dict, itemsize: int = 2) -> int:
    """Bytes of one token's index key in ONE layer (256 B at the
    published sizes): what the indexer reads a scored position."""
    return c["index_head_dim"] * itemsize


def latent_row_bytes(c: dict, itemsize: int = 2) -> int:
    """Bytes of one token's latent row in ONE layer as the pool holds it,
    in whole 128-lane tiles (640 values, 1,280 B): what the selected
    attention reads an attended position."""
    width = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    return -(-width // 128) * 128 * itemsize


def decode_weight_bytes(c: dict, itemsize: int = 2) -> float:
    """HBM bytes one decode step has to read whatever the routing: every
    matmul weight once (the dense layers, each expert layer outside its
    routed experts, the HELD routed experts, the head), the router in
    float32. The embedding is a gather."""
    dense, moe = layers(c)
    router = c["hidden_size"] * router_outputs(c)
    return (dense * dense_layer_params(c) * itemsize
            + moe * ((expert_layer_params_outside_routed(c) - router)
                     * itemsize + router * 4
                     + c["n_routed_experts"] * expert_params(c) * itemsize)
            + head_params(c) * itemsize)


def step_bytes(c: dict, runs: float, scored_per_run: float,
               attended_per_run: float) -> float:
    """HBM bytes ``runs`` decode steps have to read: the weights once a
    step; an index key a scored position and a latent row an attended
    one, ``scored_per_run`` and ``attended_per_run`` a step summed over
    its layers (the program's counters over its own step runs)."""
    return runs * (decode_weight_bytes(c)
                   + index_key_bytes(c) * scored_per_run
                   + latent_row_bytes(c) * attended_per_run)


def index_flops_per_pair(c: dict) -> float:
    """FLOPs of one (query, scored position) pair in ONE layer's indexer:
    a product over ``dI`` a head."""
    return 2.0 * c["index_n_heads"] * c["index_head_dim"]


def token_flops(c: dict, held_pairs_per_token: float) -> float:
    """Forward FLOPs of one token through the layers, attention over the
    context and the head left out: 2 x the matmul parameters it
    multiplies, an expert's a (token, held expert) pair."""
    dense, moe = layers(c)
    return 2.0 * (dense * dense_layer_params(c)
                  + moe * (expert_layer_params_outside_routed(c)
                           + held_pairs_per_token * expert_params(c)))


def context_flops(c: dict, form: str, scored: float,
                  attended: float) -> float:
    """The context's work of all layers: ``scored`` (query, visible
    position) pairs through the indexer and ``attended`` (query, selected
    position) pairs through the attention in ``form``, a layer each."""
    return c["num_hidden_layers"] * (index_flops_per_pair(c) * scored
                                     + attention_flops_per_pair(c, form)
                                     * attended)


def prefill_flops(c: dict, prompt_tokens: float, scored: float,
                  attended: float, requests: float,
                  held_pairs_per_token: float) -> float:
    """Prefill of ``prompt_tokens`` tokens in all, whose queries score
    ``scored`` and attend ``attended`` positions a layer; ``requests``
    prompts (the head runs on a prompt's last token only)."""
    return (token_flops(c, held_pairs_per_token) * prompt_tokens
            + context_flops(c, "expanded", scored, attended)
            + 2.0 * head_params(c) * requests)


def decode_flops(c: dict, out_tokens: float, scored: float,
                 attended: float, held_pairs_per_token: float) -> float:
    return ((token_flops(c, held_pairs_per_token) + 2.0 * head_params(c))
            * out_tokens + context_flops(c, "latent", scored, attended))

