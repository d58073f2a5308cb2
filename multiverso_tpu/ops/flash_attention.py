"""Pallas TPU flash attention: the framework's hot-op kernel.

The reference framework's hot loops are hand-written C++ (word2vec inner
products, ``Applications/WordEmbedding/src/wordembedding.cpp:57-168``); the
TPU-native analogue is a Pallas kernel feeding the MXU. This module provides
blockwise exact attention (Dao et al. flash schedule) as:

* :func:`flash_attention` — fused single-device attention, O(seq) memory,
  differentiable (custom VJP with a blockwise XLA backward that recomputes
  probabilities from the saved row statistics instead of storing the
  ``[seq, seq]`` score matrix).
* :func:`flash_attention_partial` — the un-normalised building block
  ``(acc, m, l)`` used by ring attention: each ring step runs the kernel on
  the resident K/V block and the cheap running-max merge happens in XLA
  while ``ppermute`` rotates the next block in over ICI.

Layout contract: ``[seq, heads, head_dim]`` at the API boundary (matching
``ops.ring_attention``); kernels run ``[heads, seq, head_dim]`` with the
head as the outer grid axis so each program works on MXU-shaped
``[block_q, head_dim] x [head_dim, block_k]`` tiles. Sequence lengths and
head_dim are padded to tile multiples; padded keys are masked, padded query
rows are sliced away on return.

On non-TPU backends the kernel runs in Pallas interpret mode, which is how
the CPU test suite validates numerics; set ``interpret=False`` to force
compilation (TPU).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x: jax.Array, size: int, axis: int) -> jax.Array:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_dim(d: int) -> int:
    """Head-dim block width: sublane-aligned d stays UNPADDED.

    Pallas pads partial lane blocks inside the VMEM pipeline for free;
    padding d to the 128 lane width in HBM instead (the r3 design)
    materialises pad/slice copies around every kernel call AND doubles
    every d-axis buffer at the common head_dim=64. Measured A/B on-chip
    at the flagship LM shape (r5, xprof device time, 2 runs each,
    docs/LM_MFU.md): lane-padded 53.94 ms vs unpadded 54.08 ms/step at
    seq 1024 — a 0.27% wash. The unpadded form is kept for its halved
    VMEM/HBM d-axis footprint, not for step time; the r4 snapshot's
    "30% of the train step" attribution was the whole flash-vs-XLA
    attention saving (78.2 -> 52.3 ms/step), not the padding delta —
    corrected here.
    ``MV_FLASH_PAD_LANES=1`` re-enables lane padding for measurement.
    Only a non-multiple-of-8 d (never seen in practice) otherwise pads,
    to the f32 sublane tile.
    """
    import os

    if os.environ.get("MV_FLASH_PAD_LANES") == "1":
        return -(-d // _LANES) * _LANES
    return d if d % 8 == 0 else -(-d // 8) * 8


def _fa_kernel(offs_ref, q_ref, k_ref, v_ref,
               o_ref, m_ref, l_ref,
               m_scr, l_scr, acc_scr,
               *, scale: float, causal: bool, normalize: bool,
               kv_len: int, block_q: int, block_k: int, precision):
    """One (head, q-block, k-block) grid step of the flash schedule.

    ``offs_ref`` (scalar prefetch) holds ``[q_base, k_base]`` — global
    position offsets so the same kernel serves both whole-sequence attention
    (zeros) and one ring step (block offsets of the resident shards).
    Running row statistics live in VMEM scratch, carried across the
    innermost (k-block) grid dimension; outputs are written on the last
    k-step.
    """
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_base = offs_ref[0]
    k_base = offs_ref[1]
    qi = pl.program_id(1)

    # Local (padded) k indices of this block and their global positions.
    k_local0 = ki * block_k
    run = jnp.logical_or(
        not causal,
        # last global q position of the block >= first global k position
        q_base + (qi + 1) * block_q - 1 >= k_base + k_local0)
    # Skip key blocks that are entirely padding.
    run = jnp.logical_and(run, k_local0 < kv_len)

    @pl.when(run)
    def _step():
        q = q_ref[0]                                    # [bq, d]
        k = k_ref[0]                                    # [bk, d]
        v = v_ref[0]                                    # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        k_local = k_local0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_local < kv_len
        if causal:
            q_pos = q_base + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, k_base + k_local <= q_pos)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, :1]                           # [bq, 1]
        m_blk = jnp.max(s, axis=1, keepdims=True)       # [bq, 1]
        m_new = jnp.maximum(m_prev, m_blk)
        m_safe = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
        corr = jnp.exp(m_prev - m_safe) * (m_prev > _NEG_INF)
        p = jnp.exp(s - m_safe) * (s > _NEG_INF)        # [bq, bk]
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32)          # [bq, d]
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        # m/l outputs are (8, block_q) tiles per (head, q-block) — the
        # minimal f32 tile the TPU lowering accepts; row 0 is the payload.
        m_ref[0, 0] = jnp.broadcast_to(m_scr[:, 0][None, :], m_ref.shape[2:])
        l_ref[0, 0] = jnp.broadcast_to(l_scr[:, 0][None, :], l_ref.shape[2:])
        if normalize:
            denom = jnp.maximum(l_scr[:, :1], 1e-20)
            o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        else:
            o_ref[0] = acc_scr[:].astype(o_ref.dtype)


def _fa_kernel_single(offs_ref, q_ref, k_ref, v_ref,
                      o_ref, m_ref, l_ref,
                      *, scale: float, causal: bool, normalize: bool,
                      kv_len: int, block_q: int, precision):
    """One-k-block forward (``nk == 1``): plain softmax, no online pass.

    With the whole K/V in one block the flash running-max/correction
    machinery (VMEM scratch carries, acc rescale per k-step) is pure
    overhead — the r5 trace measured the general kernel at ~25% of bf16
    peak at the flagship LM shape vs ~43% for the one-pass backward.
    This kernel computes max/exp/sum/divide in one sweep. Outputs match
    the general kernel's contract exactly (same m/l row-stat tiles), so
    the custom VJP and ring merges are unchanged.
    """
    q_base = offs_ref[0]
    k_base = offs_ref[1]
    qi = pl.program_id(1)
    q = q_ref[0]                                        # [bq, d]
    k = k_ref[0]                                        # [sk, d]
    v = v_ref[0]
    sk = k.shape[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32) * scale      # [bq, sk]
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (block_q, sk), 1)
    mask = k_pos < kv_len
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, sk), 0)
        mask = jnp.logical_and(mask, k_base + k_pos <= q_base + q_pos)
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)                # [bq, 1]
    m_safe = jnp.where(m <= _NEG_INF, 0.0, m)
    p = jnp.exp(s - m_safe) * (s > _NEG_INF)             # [bq, sk]
    l = jnp.sum(p, axis=1, keepdims=True)                # [bq, 1]
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)
    m_ref[0, 0] = jnp.broadcast_to(m[:, 0][None, :], m_ref.shape[2:])
    l_ref[0, 0] = jnp.broadcast_to(l[:, 0][None, :], l_ref.shape[2:])
    if normalize:
        o_ref[0] = (pv / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
    else:
        o_ref[0] = pv.astype(o_ref.dtype)


def _fa_call(q, k, v, q_base, k_base, *, causal: bool, scale: float,
             normalize: bool, block_q: int, block_k: int,
             interpret: Optional[bool], precision=None):
    """Pad to tiles, run the kernel, return ([s,h,d] out, [h,s] m, [h,s] l)."""
    if interpret is None:
        interpret = _interpret_default()
    sq, h, d = q.shape
    sk = k.shape[0]
    block_q = min(block_q, max(8, 1 << (sq - 1).bit_length()))
    block_k = min(block_k, max(_LANES, 1 << (sk - 1).bit_length()))
    sq_p = -(-sq // block_q) * block_q
    sk_p = -(-sk // block_k) * block_k
    d_p = _pad_dim(d)

    # [s, h, d] -> [h, s, d], padded
    qt = _pad_to(_pad_to(jnp.transpose(q, (1, 0, 2)), sq_p, 1), d_p, 2)
    kt = _pad_to(_pad_to(jnp.transpose(k, (1, 0, 2)), sk_p, 1), d_p, 2)
    vt = _pad_to(_pad_to(jnp.transpose(v, (1, 0, 2)), sk_p, 1), d_p, 2)
    offs = jnp.asarray([q_base, k_base], jnp.int32)

    nq = sq_p // block_q
    nk = sk_p // block_k

    # normalized attention matches the input dtype — written AT that
    # dtype inside the kernel epilogue (see out_dtype below)
    if nk == 1:
        # whole K/V in one block: plain-softmax kernel, no online pass
        single_grid = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h, nq),
            in_specs=[
                pl.BlockSpec((1, block_q, d_p), lambda hi, qi, offs: (hi, qi, 0)),
                pl.BlockSpec((1, sk_p, d_p), lambda hi, qi, offs: (hi, 0, 0)),
                pl.BlockSpec((1, sk_p, d_p), lambda hi, qi, offs: (hi, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d_p), lambda hi, qi, offs: (hi, qi, 0)),
                pl.BlockSpec((1, 1, 8, block_q), lambda hi, qi, offs: (hi, qi, 0, 0)),
                pl.BlockSpec((1, 1, 8, block_q), lambda hi, qi, offs: (hi, qi, 0, 0)),
            ],
        )
        out_dtype = q.dtype if normalize else jnp.float32
        out, m, l = pl.pallas_call(
            functools.partial(
                _fa_kernel_single, scale=scale, causal=causal,
                normalize=normalize, kv_len=sk, block_q=block_q,
                precision=precision),
            grid_spec=single_grid,
            out_shape=[
                jax.ShapeDtypeStruct((h, sq_p, d_p), out_dtype),
                jax.ShapeDtypeStruct((h, nq, 8, block_q), jnp.float32),
                jax.ShapeDtypeStruct((h, nq, 8, block_q), jnp.float32),
            ],
            interpret=interpret,
        )(offs, qt, kt, vt)
        out = jnp.transpose(out[:, :sq, :d], (1, 0, 2))
        m = m[:, :, 0, :].reshape(h, sq_p)[:, :sq]
        l = l[:, :, 0, :].reshape(h, sq_p)[:, :sq]
        return out, m, l

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, normalize=normalize,
        kv_len=sk, block_q=block_q, block_k=block_k, precision=precision)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d_p), lambda hi, qi, ki, offs: (hi, qi, 0)),
            pl.BlockSpec((1, block_k, d_p), lambda hi, qi, ki, offs: (hi, ki, 0)),
            pl.BlockSpec((1, block_k, d_p), lambda hi, qi, ki, offs: (hi, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d_p), lambda hi, qi, ki, offs: (hi, qi, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda hi, qi, ki, offs: (hi, qi, 0, 0)),
            pl.BlockSpec((1, 1, 8, block_q), lambda hi, qi, ki, offs: (hi, qi, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d_p), jnp.float32),
        ],
    )
    # normalized attention matches the input dtype — written AT that
    # dtype inside the kernel epilogue, so no f32 round trip through HBM
    # (a post-kernel convert measured ~1 ms/step at the flagship LM
    # shape). Un-normalized partials stay f32 so ring-step merges don't
    # accumulate rounding.
    out_dtype = q.dtype if normalize else jnp.float32
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((h, sq_p, d_p), out_dtype),
            jax.ShapeDtypeStruct((h, nq, 8, block_q), jnp.float32),
            jax.ShapeDtypeStruct((h, nq, 8, block_q), jnp.float32),
        ],
        interpret=interpret,
    )(offs, qt, kt, vt)
    out = jnp.transpose(out[:, :sq, :d], (1, 0, 2))
    m = m[:, :, 0, :].reshape(h, sq_p)[:, :sq]
    l = l[:, :, 0, :].reshape(h, sq_p)[:, :sq]
    return out, m, l


# ---------------------------------------------------------------------------
# public ops
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    interpret: Optional[bool] = None,
                    precision=None) -> jax.Array:
    """Fused exact attention. ``q/k/v: [seq, heads, head_dim]``.

    ``precision``: MXU pass precision for the kernel dots (``None`` =
    backend default bf16 passes, ~7e-3 abs error in f32 terms;
    ``jax.lax.Precision.HIGHEST`` for full f32).
    """
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                        precision)
    return out


def _resolve_scale(q, scale):
    return float(scale) if scale is not None else 1.0 / np.sqrt(q.shape[-1])


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               precision=None):
    s = _resolve_scale(q, scale)
    out, m, l = _fa_call(q, k, v, 0, 0, causal=causal, scale=s,
                         normalize=True, block_q=block_q, block_k=block_k,
                         interpret=interpret, precision=precision)
    return out, (q, k, v, out, m, l)


def _bwd_dq_kernel(offs_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr,
                   *, scale: float, causal: bool, kv_len: int,
                   block_q: int, block_k: int, precision):
    """dq pass: grid (h, q-block, k-block); dq accumulates in VMEM over the
    innermost k dimension. Probabilities recompute from the saved row
    logsumexp — the flash backward's no-[s,s]-buffer property.

    ``offs_ref`` (scalar prefetch) holds ``[q_base, k_base]`` — global
    position offsets, zeros for whole-sequence backward, shard offsets for
    one ring step (mirrors the forward kernel's contract)."""
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_base = offs_ref[0]
    k_base = offs_ref[1]
    qi = pl.program_id(1)
    k_local0 = ki * block_k
    run = jnp.logical_or(
        not causal,
        q_base + (qi + 1) * block_q - 1 >= k_base + k_local0)
    run = jnp.logical_and(run, k_local0 < kv_len)

    @pl.when(run)
    def _step():
        q = q_ref[0]                                    # [bq, d]
        k = k_ref[0]                                    # [bk, d]
        v = v_ref[0]
        g = g_ref[0]
        lse = lse_ref[0, 0][0]                          # [bq]
        delta = delta_ref[0, 0][0]                      # [bq]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        k_pos = k_local0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, k_base + k_pos <= q_base + q_pos)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)          # [bq, bk]
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(offs_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale: float, causal: bool, kv_len: int,
                    block_q: int, block_k: int, precision):
    """dk/dv pass: grid (h, k-block, q-block); both accumulate in VMEM over
    the innermost q dimension. ``offs_ref`` as in :func:`_bwd_dq_kernel`."""
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_base = offs_ref[0]
    k_base = offs_ref[1]
    ki = pl.program_id(1)
    k_local0 = ki * block_k
    # causal: q blocks strictly above the diagonal contribute nothing
    run = jnp.logical_or(
        not causal,
        q_base + (qi + 1) * block_q - 1 >= k_base + k_local0)
    run = jnp.logical_and(run, k_local0 < kv_len)

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        g = g_ref[0]
        lse = lse_ref[0, 0][0]
        delta = delta_ref[0, 0][0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        k_pos = k_local0 + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < kv_len
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = jnp.logical_and(mask, k_base + k_pos <= q_base + q_pos)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)  # [bq, bk]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            g, v, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)          # [bq, bk]
        ds = p * (dp - delta[:, None]) * scale           # [bq, bk]
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(offs_ref, q_ref, k_ref, v_ref, g_ref, lse_ref,
                      delta_ref, dq_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                      *, scale: float, causal: bool, kv_len: int,
                      block_q: int, precision):
    """Single-pass dq+dk+dv for the ONE-k-block case (``nk == 1``).

    When the whole K/V fits one block (seq <= block_k — the flagship LM
    shape), the two-pass backward recomputes ``s``/``p`` and ``g v^T``
    twice (dq kernel + dkv kernel: 7 block dots, 2 exp sweeps). With
    K/V resident across the q grid this kernel computes them once —
    5 dots, 1 exp — and accumulates dk/dv in VMEM over the sequential
    q dimension (the same revisited-output pattern as the dkv pass).
    Measured on-chip at the flagship LM shape this cuts the train
    step's flash backward cost (docs/LM_MFU.md r5 numbers).
    """
    qi = pl.program_id(1)
    nq = pl.num_programs(1)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_base = offs_ref[0]
    k_base = offs_ref[1]
    q = q_ref[0]                                        # [bq, d]
    k = k_ref[0]                                        # [sk, d]
    v = v_ref[0]
    g = g_ref[0]                                        # [bq, d]
    lse = lse_ref[0, 0][0]                              # [bq]
    delta = delta_ref[0, 0][0]                          # [bq]
    sk = k.shape[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32) * scale      # [bq, sk]
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (block_q, sk), 1)
    mask = k_pos < kv_len
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, sk), 0)
        mask = jnp.logical_and(mask, k_base + k_pos <= q_base + q_pos)
    p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)  # [bq, sk]
    dv_scr[:] += jax.lax.dot_general(
        p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(
        g, v, (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32)              # [bq, sk]
    ds = p * (dp - delta[:, None]) * scale               # [bq, sk]
    dq_ref[0] = jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dk_scr[:] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _stat_tiles(x, h, n_blocks, block: int):
    """[h, s] row statistic -> [h, n_blocks, 8, block] blocked tiles (row 0
    carries the payload; 8 sublanes is the minimal f32 tile height)."""
    xp = _pad_to(x, n_blocks * block, 1).reshape(h, n_blocks, 1, block)
    return jnp.broadcast_to(xp, (h, n_blocks, 8, block))


def _bwd_call(q, k, v, g, lse, delta, q_base, k_base, *, causal: bool,
              scale: float, block_q: int, block_k: int,
              interpret: Optional[bool], precision=None):
    """Backward of one (q rows x k rows) attention block pair.

    ``lse``/``delta`` are the q rows' logsumexp and ``rowsum(g*out)``
    ([h, sq]); ``q_base``/``k_base`` are the rows' global positions for
    causal masking (zeros = whole-sequence). Returns f32
    ``(dq [sq,h,d], dk [sk,h,d], dv [sk,h,d])`` — the contribution of
    THIS k-block to dq and of this q-block to dk/dv, so ring callers can
    accumulate across steps.
    """
    if interpret is None:
        interpret = _interpret_default()
    sq, h, d = q.shape
    sk = k.shape[0]
    block_q = min(block_q, max(8, 1 << (sq - 1).bit_length()))
    block_k = min(block_k, max(_LANES, 1 << (sk - 1).bit_length()))
    sq_p = -(-sq // block_q) * block_q
    sk_p = -(-sk // block_k) * block_k
    d_p = _pad_dim(d)
    nq = sq_p // block_q
    nk = sk_p // block_k

    qt = _pad_to(_pad_to(jnp.transpose(q, (1, 0, 2)), sq_p, 1), d_p, 2)
    kt = _pad_to(_pad_to(jnp.transpose(k, (1, 0, 2)), sk_p, 1), d_p, 2)
    vt = _pad_to(_pad_to(jnp.transpose(v, (1, 0, 2)), sk_p, 1), d_p, 2)
    gt = _pad_to(_pad_to(jnp.transpose(g, (1, 0, 2)), sq_p, 1), d_p, 2)
    # padded q rows get +LARGE lse so their recomputed p == 0
    lse_p = jnp.where((jnp.arange(sq_p) < sq)[None, :],
                      _pad_to(lse, sq_p, 1), -_NEG_INF)
    lse_t = _stat_tiles(lse_p, h, nq, block_q)
    delta_t = _stat_tiles(_pad_to(delta, sq_p, 1), h, nq, block_q)
    offs = jnp.asarray([q_base, k_base], jnp.int32)

    if nk == 1:
        # whole K/V resident -> fused single-pass backward (5 dots and
        # one exp sweep vs the two-pass 7 dots / two sweeps); dk/dv
        # accumulate across the sequential q grid dimension
        fq_spec = pl.BlockSpec((1, block_q, d_p), lambda hi, a, offs: (hi, a, 0))
        fk_spec = pl.BlockSpec((1, sk_p, d_p), lambda hi, a, offs: (hi, 0, 0))
        fstat_spec = pl.BlockSpec((1, 1, 8, block_q),
                                  lambda hi, a, offs: (hi, a, 0, 0))
        fused_grid = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h, nq),
            in_specs=[fq_spec, fk_spec, fk_spec, fq_spec, fstat_spec,
                      fstat_spec],
            out_specs=[
                pl.BlockSpec((1, block_q, d_p), lambda hi, a, offs: (hi, a, 0)),
                pl.BlockSpec((1, sk_p, d_p), lambda hi, a, offs: (hi, 0, 0)),
                pl.BlockSpec((1, sk_p, d_p), lambda hi, a, offs: (hi, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((sk_p, d_p), jnp.float32),
                            pltpu.VMEM((sk_p, d_p), jnp.float32)],
        )
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_fused_kernel, scale=scale, causal=causal,
                              kv_len=sk, block_q=block_q,
                              precision=precision),
            grid_spec=fused_grid,
            out_shape=[
                jax.ShapeDtypeStruct((h, sq_p, d_p), jnp.float32),
                jax.ShapeDtypeStruct((h, sk_p, d_p), jnp.float32),
                jax.ShapeDtypeStruct((h, sk_p, d_p), jnp.float32),
            ],
            interpret=interpret,
        )(offs, qt, kt, vt, gt, lse_t, delta_t)
        dq = jnp.transpose(dq[:, :sq, :d], (1, 0, 2))
        dk = jnp.transpose(dk[:, :sk, :d], (1, 0, 2))
        dv = jnp.transpose(dv[:, :sk, :d], (1, 0, 2))
        return dq, dk, dv

    q_spec = pl.BlockSpec((1, block_q, d_p),
                          lambda hi, a, b, offs: (hi, a, 0))
    k_spec = pl.BlockSpec((1, block_k, d_p),
                          lambda hi, a, b, offs: (hi, b, 0))
    stat_spec = pl.BlockSpec((1, 1, 8, block_q),
                             lambda hi, a, b, offs: (hi, a, 0, 0))

    dq_grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, stat_spec, stat_spec],
        out_specs=pl.BlockSpec((1, block_q, d_p),
                               lambda hi, a, b, offs: (hi, a, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d_p), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          kv_len=sk, block_q=block_q, block_k=block_k,
                          precision=precision),
        grid_spec=dq_grid,
        out_shape=jax.ShapeDtypeStruct((h, sq_p, d_p), jnp.float32),
        interpret=interpret,
    )(offs, qt, kt, vt, gt, lse_t, delta_t)

    # dk/dv grid: second axis is the K block, innermost is the Q block
    q_spec2 = pl.BlockSpec((1, block_q, d_p),
                           lambda hi, a, b, offs: (hi, b, 0))
    k_spec2 = pl.BlockSpec((1, block_k, d_p),
                           lambda hi, a, b, offs: (hi, a, 0))
    stat_spec2 = pl.BlockSpec((1, 1, 8, block_q),
                              lambda hi, a, b, offs: (hi, b, 0, 0))
    dkv_grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(h, nk, nq),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, stat_spec2,
                  stat_spec2],
        out_specs=[
            pl.BlockSpec((1, block_k, d_p),
                         lambda hi, a, b, offs: (hi, a, 0)),
            pl.BlockSpec((1, block_k, d_p),
                         lambda hi, a, b, offs: (hi, a, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d_p), jnp.float32),
                        pltpu.VMEM((block_k, d_p), jnp.float32)],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          kv_len=sk, block_q=block_q, block_k=block_k,
                          precision=precision),
        grid_spec=dkv_grid,
        out_shape=[
            jax.ShapeDtypeStruct((h, sk_p, d_p), jnp.float32),
            jax.ShapeDtypeStruct((h, sk_p, d_p), jnp.float32),
        ],
        interpret=interpret,
    )(offs, qt, kt, vt, gt, lse_t, delta_t)

    dq = jnp.transpose(dq[:, :sq, :d], (1, 0, 2))
    dk = jnp.transpose(dk[:, :sk, :d], (1, 0, 2))
    dv = jnp.transpose(dv[:, :sk, :d], (1, 0, 2))
    return dq, dk, dv


def flash_attention_partial_bwd(q, k, v, g, lse, delta, q_base, k_base,
                                causal: bool = False,
                                scale: Optional[float] = None,
                                block_q: int = 1024, block_k: int = 1024,
                                interpret: Optional[bool] = None,
                                precision=None):
    """One ring step's backward: Pallas dq/dk/dv for a (q-shard, k-shard)
    pair in GLOBAL coordinates (the gradient twin of
    :func:`flash_attention_partial`). ``lse = m + log l`` comes from the
    forward ring's merged statistics; ``delta = rowsum(g * out)`` from the
    normalized output. Returns f32 partials for the caller to accumulate.
    """
    s = _resolve_scale(q, scale)
    return _bwd_call(q, k, v, g, lse, delta, q_base, k_base, causal=causal,
                     scale=s, block_q=block_q, block_k=block_k,
                     interpret=interpret, precision=precision)


def _flash_bwd(causal, scale, block_q, block_k, interpret, precision, res, g):
    """Pallas blockwise backward from saved row stats (no [s,s] buffer).

    Standard flash backward: with row logsumexp ``L = m + log l`` the
    probabilities of any k-block recompute as ``exp(s - L)``; then
    ``dv = p^T g``, ``ds = p * (g v^T - rowsum(g*o))``, ``dq = ds k``,
    ``dk = ds^T q`` — dq in one kernel (k innermost), dk/dv in a second
    (q innermost), both accumulating in VMEM scratch.
    """
    q, k, v, out, m, l = res
    s_scale = _resolve_scale(q, scale)
    lse = m + jnp.log(jnp.maximum(l, 1e-20))                    # [h, sq]
    delta = jnp.einsum("shd,shd->hs", g.astype(jnp.float32),
                       out.astype(jnp.float32))                 # [h, sq]
    dq, dk, dv = _bwd_call(q, k, v, g, lse, delta, 0, 0, causal=causal,
                           scale=s_scale, block_q=block_q, block_k=block_k,
                           interpret=interpret, precision=precision)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# Measured on-chip crossover (docs/TPU_VALIDATE.json — measured on an
# earlier device set-up; crossover to be re-measured by a benchmark PR):
# XLA-fused reference attention wins below ~1.5k sequence, the Pallas
# kernel above. Override by passing min_flash_seq to best_attention (or
# monkeypatching this).
FLASH_CROSSOVER_SEQ = 1536


def best_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = False, scale: Optional[float] = None,
                   min_flash_seq: Optional[int] = None,
                   **flash_kwargs) -> jax.Array:
    """Crossover dispatch: never slower than XLA at any sequence length.

    Below the measured crossover the XLA-fused reference attention is
    faster than the Pallas kernel (kernel launch + un-fused epilogue
    dominate at small seq); at/above it the flash schedule's O(seq) memory
    and tiling win 3-5x. ``TransformerConfig(attention="flash")`` routes
    here so users can't be slowed down by picking the kernel at short
    sequences; ``attention="flash_force"`` pins the kernel.
    """
    thr = FLASH_CROSSOVER_SEQ if min_flash_seq is None else int(min_flash_seq)
    # Off-TPU the kernel only exists in Pallas INTERPRET mode (a numerics
    # test vehicle, orders of magnitude slower than XLA) — the crossover
    # constants are TPU measurements, so the dispatch answer off-TPU is
    # always the XLA path unless the caller explicitly asks for the
    # interpreted kernel (interpret=True, as the tests do).
    kernel_viable = (not _interpret_default()
                     or flash_kwargs.get("interpret"))
    if max(q.shape[0], k.shape[0]) < thr or not kernel_viable:
        from .ring_attention import reference_attention

        return reference_attention(q, k, v, causal=causal, scale=scale)
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           **flash_kwargs)


def flash_attention_partial(
        q: jax.Array, k: jax.Array, v: jax.Array,
        q_base, k_base, causal: bool = False,
        scale: Optional[float] = None,
        block_q: int = 1024, block_k: int = 1024,
        interpret: Optional[bool] = None, precision=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Un-normalised flash block: returns ``(acc [s,h,d], m [h,s], l [h,s])``.

    ``q_base``/``k_base`` are the global positions of ``q[0]``/``k[0]``
    (traced scalars are fine) — ring attention passes the shard offsets so
    causal masking applies in global coordinates.
    """
    s = _resolve_scale(q, scale)
    return _fa_call(q, k, v, q_base, k_base, causal=causal, scale=s,
                    normalize=False, block_q=block_q, block_k=block_k,
                    interpret=interpret, precision=precision)


def merge_partials(m_a, l_a, acc_a, m_b, l_b, acc_b):
    """Combine two flash partials (the associative running-max merge)."""
    m = jnp.maximum(m_a, m_b)
    m_safe = jnp.where(m <= _NEG_INF, 0.0, m)
    ca = jnp.exp(m_a - m_safe) * (m_a > _NEG_INF)
    cb = jnp.exp(m_b - m_safe) * (m_b > _NEG_INF)
    l = l_a * ca + l_b * cb
    acc = (acc_a * ca.transpose(1, 0)[:, :, None]
           + acc_b * cb.transpose(1, 0)[:, :, None])
    return m, l, acc
