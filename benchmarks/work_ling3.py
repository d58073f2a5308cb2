"""Work counts of a ``bailing_hybrid`` share (configuration
``ling3-flash-ep4``), from the configuration's sizes: what
``ling3_serve_mfu``, ``ling3_decode_step_roofline``,
``ling3_prefill_chunk_mfu``, ``kda_step_roofline`` and
``kda_chunk_roofline`` divide by a peak of ``work.PEAKS`` and a time.
Like ``work.py`` they are the yardstick: the least work the algorithm
needs, whatever the program does.

A "token" here is one row through the ``num_hidden_layers`` layers held
on this chip. A layer's attention is MLA where ``(i + 1) %
layer_group_size == 0`` and KDA otherwise; its FFN a dense SwiGLU in the
``first_k_dense_replace`` leading layers and, in the expert layers, the
router, the shared expert and the token's picks among the HELD routed
experts (``held_pairs_per_token`` a layer, the program's counter: the
held experts run over every row, which is not counted).

What a SEQUENCE holds in a KDA layer is a float32 state ``[H, dh, dh]``
and ``taps - 1`` rows of ``[q~, k~, v~]``: fixed bytes a slot, read and
written once by every step in which the slot is live.
"""

from __future__ import annotations

from benchmarks.work_longcat import attention_flops_per_pair, head_params


def layer_kinds(c: dict) -> tuple:
    """``(KDA layers, MLA layers)`` held here."""
    mla = sum((i + 1) % c["layer_group_size"] == 0
              for i in range(c["num_hidden_layers"]))
    return c["num_hidden_layers"] - mla, mla


def ffn_kinds(c: dict) -> tuple:
    """``(dense layers, expert layers)`` held here."""
    dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    return dense, c["num_hidden_layers"] - dense


def router_outputs(c: dict) -> int:
    return int(c.get("published", {}).get("num_experts", c["num_experts"]))


def kda_params(c: dict) -> int:
    """Matmul parameters a token multiplies in one KDA sublayer: W_qkv,
    W_f, W_g, W_o and w_beta."""
    D, HD = c["hidden_size"], c["num_attention_heads"] * c["head_dim"]
    return D * 3 * HD + 3 * D * HD + D * c["num_attention_heads"]


def mla_params(c: dict) -> int:
    """One MLA sublayer without a query latent: W_q, W_kva, W_kvb (once
    a token in both attention forms), W_o and the head gate."""
    D, H = c["hidden_size"], c["num_attention_heads"]
    rkv, dn, dr, dv = c["kv_lora_rank"], c["qk_nope_head_dim"], \
        c["qk_rope_head_dim"], c["v_head_dim"]
    return (D * H * (dn + dr) + D * (rkv + dr) + rkv * H * (dn + dv)
            + H * dv * D + D * H)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def attention_params(c: dict) -> int:
    """All attention sublayers held here, both kinds."""
    kda, mla = layer_kinds(c)
    return kda * kda_params(c) + mla * mla_params(c)


def kda_recurrence_flops(c: dict) -> float:
    """FLOPs of the gated delta rule for ONE token in ONE KDA layer, all
    heads, as the recurrence states it: the decay (1 a state element),
    ``S^T k``, the rank-one write and ``S^T q`` (2 each), and the
    convolution (2 a tap and channel)."""
    H, dh = c["num_attention_heads"], c["head_dim"]
    return 7.0 * H * dh * dh + 2.0 * c["short_conv_kernel_size"] * 3 * H * dh


def slot_state_bytes(c: dict) -> int:
    """Bytes a slot holds in the KDA layers whatever its length: the
    float32 states and the bfloat16 conv tails."""
    H, dh = c["num_attention_heads"], c["head_dim"]
    kda, _ = layer_kinds(c)
    return kda * (H * dh * dh * 4
                  + (c["short_conv_kernel_size"] - 1) * 3 * H * dh * 2)


def cache_row_bytes(c: dict, itemsize: int = 2) -> int:
    """Bytes of one token's cache rows: ``(rkv + dr)`` values a latent
    layer (1,152 B with one such layer)."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * itemsize \
        * layer_kinds(c)[1]


def decode_weight_bytes(c: dict, itemsize: int = 2) -> float:
    """HBM bytes one decode step has to read whatever the routing: every
    matmul weight once (attention of both kinds, the dense FFNs, the
    shared and the HELD routed experts, the head), the router in
    float32. The embedding is a gather."""
    dense, moe = ffn_kinds(c)
    D = c["hidden_size"]
    return (attention_params(c) * itemsize
            + dense * 3 * D * c["intermediate_size"] * itemsize
            + moe * (D * router_outputs(c) * 4
                     + (c.get("num_shared_experts", 1) + c["num_experts"])
                     * expert_params(c) * itemsize)
            + head_params(c) * itemsize)


def token_flops(c: dict, held_pairs_per_token: float) -> float:
    """Forward FLOPs of one token through the layers, MLA over the
    context and the head left out: 2 x the matmul parameters it
    multiplies (an expert's a (token, held expert) pair), and the KDA
    recurrence."""
    dense, moe = ffn_kinds(c)
    D = c["hidden_size"]
    return (2.0 * (attention_params(c)
                   + dense * 3 * D * c["intermediate_size"]
                   + moe * (D * router_outputs(c)
                            + (c.get("num_shared_experts", 1)
                               + held_pairs_per_token) * expert_params(c)))
            + layer_kinds(c)[0] * kda_recurrence_flops(c))


def prefill_flops(c: dict, prompt_tokens: float, prefill_context: float,
                  requests: float, held_pairs_per_token: float) -> float:
    """Prefill of ``prompt_tokens`` tokens in all, ``prefill_context``
    (query, visible) pairs in the latent layers, ``requests`` prompts
    (the head runs on a prompt's last token only)."""
    return (token_flops(c, held_pairs_per_token) * prompt_tokens
            + attention_flops_per_pair(c, "expanded") * layer_kinds(c)[1]
            * prefill_context
            + 2.0 * head_params(c) * requests)


def decode_flops(c: dict, out_tokens: float, decode_context: float,
                 held_pairs_per_token: float) -> float:
    return ((token_flops(c, held_pairs_per_token) + 2.0 * head_params(c))
            * out_tokens
            + attention_flops_per_pair(c, "latent") * layer_kinds(c)[1]
            * decode_context)


def decode_tokens(c: dict, counters: dict):
    """Tokens the step program decoded in the window (a live slot and
    step each), from the program's own counters: the (token, KDA layer)
    pairs it counted, a KDA layer, less the prompt tokens the chunk
    program ran. None without the counters."""
    eng = counters.get("engine") or {}
    pairs, prefill = eng.get("kda_layer_tokens"), eng.get("prefill_tokens")
    if pairs is None or prefill is None:
        return None
    return pairs / layer_kinds(c)[0] - prefill
