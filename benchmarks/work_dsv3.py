"""Work counts of a DeepSeek-V3-architecture share (configuration
``deepseek_v3``; ``dots-vlm1-ep16``), from the configuration's sizes: what
``dotsvlm1_serve_mfu``, ``dotsvlm1_decode_step_roofline`` and
``dotsvlm1_prefill_chunk_mfu`` divide by a peak of ``work.PEAKS`` and a
time. Like ``work.py`` they are the yardstick: the least work the
algorithm needs, whatever the program does.

A "token" here is one row through the ``num_hidden_layers`` layers held
on this chip: an MLA sublayer each, then a dense SwiGLU FFN in the
``first_k_dense_replace`` leading layers and, in the expert layers, the
router, the shared expert and the token's picks among the HELD routed
experts (``held_pairs_per_token`` a layer, the program's counter: the
held experts run over every row, which is not counted).
"""

from __future__ import annotations

# the MLA sublayer, a (query, token) pair in both attention forms and
# the head count alike for every model with these keys
from benchmarks.work_longcat import (attention_flops_per_pair, head_params,
                                     mla_params)


def layers(c: dict) -> tuple:
    """``(dense layers, expert layers)`` held here."""
    dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    return dense, c["num_hidden_layers"] - dense


def router_outputs(c: dict) -> int:
    return int(c.get("published", {}).get("n_routed_experts",
                                          c["n_routed_experts"]))


def expert_params(c: dict) -> int:
    """One routed expert (the shared expert is ``n_shared_experts`` of
    them wide)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_layer_params(c: dict) -> int:
    return mla_params(c) + 3 * c["hidden_size"] * c["intermediate_size"]


def expert_layer_params_outside_routed(c: dict) -> int:
    """MLA, the router and the shared expert: what every token of an
    expert layer multiplies whatever it picks."""
    return (mla_params(c) + c["hidden_size"] * router_outputs(c)
            + c["n_shared_experts"] * expert_params(c))


def cache_row_bytes(c: dict, itemsize: int = 2) -> int:
    """Bytes of one token's cache rows, all layers held here: ``(rkv +
    dr)`` values a layer (6,912 B at the published sizes, 6 layers)."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * itemsize \
        * c["num_hidden_layers"]


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


def token_flops(c: dict, held_pairs_per_token: float) -> float:
    """Forward FLOPs of one token through the layers, attention over the
    context and the head left out: 2 x the matmul parameters it
    multiplies, an expert's a (token, held expert) pair,
    ``held_pairs_per_token`` of them an expert layer."""
    dense, moe = layers(c)
    return 2.0 * (dense * dense_layer_params(c)
                  + moe * (expert_layer_params_outside_routed(c)
                           + held_pairs_per_token * expert_params(c)))


def prefill_flops(c: dict, prompt_tokens: float, prefill_context: float,
                  requests: float, held_pairs_per_token: float) -> float:
    """Prefill of ``prompt_tokens`` tokens in all, ``prefill_context``
    (query, visible) pairs, ``requests`` prompts (the head runs on a
    prompt's last token only)."""
    return (token_flops(c, held_pairs_per_token) * prompt_tokens
            + attention_flops_per_pair(c, "expanded")
            * c["num_hidden_layers"] * prefill_context
            + 2.0 * head_params(c) * requests)


def decode_flops(c: dict, out_tokens: float, decode_context: float,
                 held_pairs_per_token: float) -> float:
    return ((token_flops(c, held_pairs_per_token) + 2.0 * head_params(c))
            * out_tokens
            + attention_flops_per_pair(c, "latent") * c["num_hidden_layers"]
            * decode_context)
