"""Compute ops: embedding gather/scatter, ring attention, pallas kernels."""

from .embedding import embedding_lookup, scatter_add_rows, segment_mean_rows
from .flash_attention import (flash_attention, flash_attention_partial,
                              merge_partials)
from .kda import kda_chunk, kda_step, short_conv_chunk, short_conv_step
from .paged_attention import paged_mq_attention
from .moe import (EXPERT_AXIS, held_expert_layer, init_moe_params, mlp_expert,
                  moe_apply, route_group_limited, route_topk, swiglu,
                  top1_gating)
from .ring_attention import (reference_attention, ring_attention,
                             ring_prefill_attention)
from .ulysses import ulysses_attention, ulysses_prefill_attention

__all__ = [
    "embedding_lookup",
    "scatter_add_rows",
    "segment_mean_rows",
    "flash_attention",
    "flash_attention_partial",
    "merge_partials",
    "kda_chunk",
    "kda_step",
    "short_conv_chunk",
    "short_conv_step",
    "paged_mq_attention",
    "EXPERT_AXIS",
    "held_expert_layer",
    "route_group_limited",
    "route_topk",
    "swiglu",
    "init_moe_params",
    "mlp_expert",
    "moe_apply",
    "top1_gating",
    "reference_attention",
    "ring_attention",
    "ring_prefill_attention",
    "ulysses_attention",
    "ulysses_prefill_attention",
]
