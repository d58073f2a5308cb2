"""Work counts of a LongCat-Flash share (configuration ``longcat_flash``),
from the configuration's sizes: what ``longcat_serve_mfu``,
``longcat_decode_step_roofline`` and ``longcat_prefill_chunk_mfu`` divide
by a peak of ``work.PEAKS`` and a time. Like ``work.py`` they are the
yardstick: the least work the algorithm needs, whatever the program does.

A "token" here is one row through the ``num_layers`` double blocks held
on this chip (two MLA sublayers, two dense FFNs, one expert layer each).
"""

from __future__ import annotations


def _sizes(c: dict) -> tuple:
    return (c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"],
            c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"])


def router_outputs(c: dict) -> int:
    return int(c.get("published", {}).get(
        "n_routed_experts", c["n_routed_experts"])) + c["zero_expert_num"]


def mla_params(c: dict) -> int:
    """Matmul parameters a token multiplies in one MLA sublayer: W_qa,
    W_qb, W_kva, W_kvb and W_o. W_kvb counts once a token in both forms:
    expanded, the token's latent becomes its keys and values; in the
    decode form its key half is absorbed into the query and its value
    half into the output, the same ``rkv x H x (dn + dv)`` products."""
    D, H, rq, rkv, dn, dr, dv = _sizes(c)
    return (D * rq + rq * H * (dn + dr) + D * (rkv + dr)
            + rkv * H * (dn + dv) + H * dv * D)


def block_params_outside_experts(c: dict) -> int:
    """Two MLA sublayers, two dense SwiGLU FFNs and the router."""
    D = c["hidden_size"]
    return (2 * mla_params(c) + 2 * 3 * D * c["ffn_hidden_size"]
            + D * router_outputs(c))


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["expert_ffn_hidden_size"]


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def attention_flops_per_pair(c: dict, form: str) -> float:
    """FLOPs of one (query token, visible token) pair in ONE sublayer,
    all heads. ``expanded`` (prefill): scores over ``dn + dr`` and values
    over ``dv`` a head. ``latent`` (decode): scores over the cache row
    (``rkv + dr``) and values over the latent (``rkv``) a head: more
    products a pair, which is the price of reading ``rkv + dr`` values a
    token and not ``H x (dn + dr + dv)``."""
    _, H, _, rkv, dn, dr, dv = _sizes(c)
    if form == "expanded":
        return 2.0 * H * (dn + dr + dv)
    if form == "latent":
        return 2.0 * H * (rkv + dr + rkv)
    raise ValueError(form)


def cache_row_bytes(c: dict, itemsize: int = 2) -> int:
    """Bytes of one token's cache rows, all sublayers held here:
    ``(rkv + dr)`` values a sublayer (9,216 B at the published sizes)."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * itemsize \
        * 2 * c["num_layers"]


def decode_weight_bytes(c: dict, itemsize: int = 2) -> float:
    """HBM bytes one decode step has to read whatever the routing: every
    matmul weight once (blocks outside the experts, the HELD experts,
    the head), the router in float32. The embedding is a gather."""
    L, D = c["num_layers"], c["hidden_size"]
    router = D * router_outputs(c)
    return (L * ((block_params_outside_experts(c) - router) * itemsize
                 + router * 4
                 + c["n_routed_experts"] * expert_params(c) * itemsize)
            + head_params(c) * itemsize)


def token_flops(c: dict, held_pairs_per_token: float) -> float:
    """Forward FLOPs of one token through the blocks, attention over the
    context and the head left out: 2 x the matmul parameters it
    multiplies outside the experts, and 2 x an expert's a (token, held
    expert) pair, ``held_pairs_per_token`` of them a layer (the
    program's counter; identity experts multiply nothing)."""
    return 2.0 * c["num_layers"] * (
        block_params_outside_experts(c)
        + held_pairs_per_token * expert_params(c))


def prefill_flops(c: dict, prompt_tokens: float, prefill_context: float,
                  requests: float, held_pairs_per_token: float) -> float:
    """Prefill of ``prompt_tokens`` tokens in all, ``prefill_context``
    (query, visible) pairs, ``requests`` prompts (the head runs on a
    prompt's last token only)."""
    return (token_flops(c, held_pairs_per_token) * prompt_tokens
            + attention_flops_per_pair(c, "expanded") * 2 * c["num_layers"]
            * prefill_context
            + 2.0 * head_params(c) * requests)


def decode_flops(c: dict, out_tokens: float, decode_context: float,
                 held_pairs_per_token: float) -> float:
    return ((token_flops(c, held_pairs_per_token) + 2.0 * head_params(c))
            * out_tokens
            + attention_flops_per_pair(c, "latent") * 2 * c["num_layers"]
            * decode_context)
