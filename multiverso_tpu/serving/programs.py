"""The seam between :class:`DecodeEngine` and the model it serves.

The engine owns scheduling: slots, the block allocator and the block
tables, chunked admission, the decode loop, booking, stats. The MODEL
owns what a cache row is and how a token is computed against it. At
construction the engine resolves its own knobs into an
:class:`EngineSpec` and asks ``lm.serving_programs(spec)`` for a
:class:`ServingPrograms`: the cache layout (one device array a *pool*)
and the jitted programs over it. ``TransformerLM`` answers with two
``[L, N + 1, Bs, d_model]`` K/V pools (and two scale arrays under
``kv_quant="int8"``); ``LongCatLM`` and ``DeepSeekV3LM`` with one
``[sublayers, N + 1, Bs, 640]`` latent pool (a 576-value row padded to
whole 128-lane tiles) and a small array of counters; ``LingLM`` with a
latent pool for its one latent layer in six, then two pools indexed by
SLOT and not by block (``[6, slots, 32, 128, 128]`` float32 recurrent
states, ``[6, slots, 3, 12288]`` convolution tails), then the counters.

Two kinds of pool. A BLOCK pool's second axis is the block id
(``N + 1``, block 0 scratch): the engine's allocator hands blocks out and
the block tables name them. **Pool 0 is always a block pool**: the engine
reads block size, block shape and dtype off ``_pools[0]`` (admission
bounds, the transfer plane). A SLOT pool's second axis is the slot: no
allocator, no table; its life cycle is the model's programs' own: a
slot's rows are reset by the chunk that starts a prompt (``off == 0``),
carried by later chunks and steps, left bit-identical by a step in which
the slot is not ``active`` and by a chunk's padded rows, and never read
between requests (warm-up's chunk, whose table names only the scratch
block, leaves them as they are). ``bytes_per_slot`` is what ``stats()``
needs to account them.

Calling convention, the same for every model (``*pools`` in the order
of ``ServingPrograms.pools``; block tables, tokens, positions and masks
are traced data of fixed shape, so each program compiles once):

===========  =====================================================  =========================
program      arguments                                              returns
===========  =====================================================  =========================
``step``     ``params, *pools, tables, tok, pos, active``           ``*pools, next_tok, _``
``chunk``    ``params, *pools, tables, slot, toks, off, n``         ``*pools, last_logits``
``chunk_sp``  as ``chunk`` (sequence-parallel, ``budget * tp`` toks)  as ``chunk``
``verify``   ``params, *pools, tables, toks, pos, active, n_valid`` ``*pools, next_tok``
``cow``      ``*pools, src, dst``                                   ``*pools``
``fetch``    ``*pools, block``                                      one block's slice a pool
``splice``   ``*pools, block, *slices``                             ``*pools``
===========  =====================================================  =========================

A model leaves a program it lacks ``None`` and REFUSES the engine
feature that needs it at construction (:func:`refuse`), by name: a
feature is never run wrong.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

from ..log import Log


@dataclass(frozen=True)
class EngineSpec:
    """What the engine resolved from its config and flags, handed to the
    model's ``serving_programs``."""

    name: str
    slots: int
    max_prompt: int
    max_new: int
    cache_len: int            # T = max_prompt + max_new
    block_size: int           # token positions a block
    blocks_per_seq: int       # M = ceil(T / block_size)
    pool_blocks: int          # usable blocks N (the pool holds N + 1)
    budget: int               # prefill chunk tokens
    prefix: bool              # prefix cache on: needs cow (+ fetch/splice)
    tp: int                   # decode mesh width
    mesh: Any                 # the decode mesh (tp > 1) or None
    kv_quant: str             # "none" | "int8"
    param_quant: str          # "none" | "int8"
    spec_k: int               # speculative window; 0 = off
    prefill_sp: str           # "none" | "ring" | "ulysses"
    donate: bool              # the backend aliases donated inputs


@dataclass
class ServingPrograms:
    """A model's answer: cache layout, programs, and how to pin."""

    pools: Tuple[Tuple[tuple, Any], ...]     # (shape, dtype) a pool
    bytes_per_block: int                     # device bytes a block, all pools
    step: Callable
    chunk: Optional[Callable] = None
    chunk_sp: Optional[Callable] = None
    verify: Optional[Callable] = None
    cow: Optional[Callable] = None
    fetch: Optional[Callable] = None
    splice: Optional[Callable] = None
    # snapshot value -> what the programs take as ``params`` (a replica
    # on one device, a reshard onto the decode mesh, or the value itself)
    pin: Callable[[Any], Any] = lambda value: value
    param_shardings: Any = None              # decode-mesh pin target
    # placement of each pool; None = the first device
    pool_targets: Optional[Tuple[Any, ...]] = None
    # a pool the programs ACCUMULATE counters into (never donated, so
    # any thread may read the newest), and what its deltas mean
    counter_pool: Optional[int] = None
    counters: Optional[Callable[[Any], dict]] = None
    # the pools that hold one scale a (layer, block) under a quantized
    # cache, zero until the block is written: ``stats()`` counts the
    # blocks with a scale in any of them (``quant_scale_blocks``)
    scale_pools: Tuple[int, ...] = ()
    # device bytes a SLOT holds whatever its length, all slot pools
    # (a recurrent state): ``stats()["slot_state_bytes_per_device"]``
    bytes_per_slot: int = 0


def refuse(who: str, spec: EngineSpec, **lacking: str) -> None:
    """Fail construction when ``spec`` asks for a feature named in
    ``lacking`` (feature -> why the model lacks it)."""
    asked = {"prefix_cache": spec.prefix,
             "kv_quant": spec.kv_quant != "none",
             "param_quant": spec.param_quant != "none",
             "decode_tp": spec.tp > 1,
             "spec_k": spec.spec_k > 0,
             "prefill_sp": spec.prefill_sp != "none"}
    for feature, why in lacking.items():
        if asked[feature]:
            Log.fatal(f"{who}: {feature} is not supported by this model: "
                      f"{why}")
