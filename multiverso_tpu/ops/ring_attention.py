"""Ring attention: sequence-parallel exact attention over the ``seq`` axis.

The reference predates transformers and has no sequence dimension (survey
§5.7); long-context support is a first-class requirement of this framework,
so it is built on the same substrate as everything else: sharded arrays +
ICI collectives. Q/K/V are sharded along sequence over the ``seq`` mesh
axis; each step computes one block of scores flash-style (running max /
normaliser accumulation, so the full [seq, seq] score matrix never
materialises) while K/V blocks rotate around the ring via ``ppermute`` —
compute overlaps the neighbour exchange, the classic ring-attention
schedule (Liu et al., 2023).

Differentiable end-to-end (autodiff through the scan + ppermute), causal or
full; exact (not windowed) attention.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..topology import SEQ_AXIS
from .flash_attention import flash_attention_partial, merge_partials

from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def _block_attn(q, k, v, q_pos, k_pos, causal, scale, m, l, acc):
    """One flash-attention block accumulation step.

    q: [sq, h, d]; k/v: [sk, h, d]; positions: [sq], [sk].
    m/l: [h, sq] running max / normaliser; acc: [sq, h, d].
    """
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = (k_pos[None, :] <= q_pos[:, None])[None, :, :]
        scores = jnp.where(mask, scores, _NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    # guard fully-masked rows (m_new == -inf) against NaNs
    m_safe = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
    correction = jnp.exp(m - m_safe) * (m > _NEG_INF)
    p = jnp.exp(scores - m_safe[:, :, None]) * (scores > _NEG_INF)
    l_new = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("hqk,khd->qhd", p, v)
    acc_new = acc * correction.transpose(1, 0)[:, :, None] + pv
    return m_new, l_new, acc_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    axis: str = SEQ_AXIS,
    causal: bool = False,
    scale: Optional[float] = None,
    impl: str = "xla",
) -> jax.Array:
    """Exact attention with sequence sharded over ``axis``.

    Shapes: q/k/v ``[seq, heads, dim]`` (batch handled via vmap by callers),
    sharded ``P(axis, None, None)``. Returns same shape/sharding as ``q``.

    ``impl="pallas"`` runs each ring step's block attention as the Pallas
    flash kernel (``ops.flash_attention_partial``) — the MXU-heavy part —
    with the cheap running-max merge in XLA while ``ppermute`` rotates K/V.
    Differentiable: the custom VJP runs a SECOND ring that rotates
    ``(k, v, dk, dv)`` together while the Pallas backward kernels
    (``flash_attention_partial_bwd``) produce each (q-shard, k-shard)
    pair's gradient contribution — dk/dv accumulators arrive back home
    after a full revolution, and activation memory stays O(seq/n) per
    device (only the forward's row statistics are saved; probabilities
    recompute blockwise from the logsumexp).
    """
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown ring attention impl {impl!r}")
    n_blocks = int(mesh.shape[axis])
    seq = q.shape[0]
    if seq % n_blocks != 0:
        raise ValueError(f"seq {seq} must divide over {n_blocks} ring steps")
    block = seq // n_blocks
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    spec = P(axis, None, None)
    perm = [(i, (i + 1) % n_blocks) for i in range(n_blocks)]

    def _fwd_shard(q_blk, k_blk, v_blk, my_idx):
        """One shard's forward ring; returns (out, lse [h, block])."""
        h = q_blk.shape[1]
        q_pos = my_idx * block + jnp.arange(block)
        # f32 carry regardless of input dtype: both impls produce f32
        # un-normalized partials (bf16 inputs would hit a fori_loop carry
        # dtype mismatch otherwise); cast back to q.dtype at the end
        m0 = jnp.full((h, block), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((h, block), jnp.float32)
        acc0 = jnp.zeros(q_blk.shape, jnp.float32)

        def body(step, carry):
            m, l, acc, k_cur, v_cur = carry
            # after `step` rotations, we hold the block that started at
            # ring position (my_idx - step) mod n
            src = jnp.mod(my_idx - step, n_blocks)
            if impl == "pallas":
                acc_b, m_b, l_b = flash_attention_partial(
                    q_blk, k_cur, v_cur, my_idx * block, src * block,
                    causal=causal, scale=scale)
                m, l, acc = merge_partials(m, l, acc, m_b, l_b, acc_b)
            else:
                k_pos = src * block + jnp.arange(block)
                m, l, acc = _block_attn(q_blk, k_cur, v_cur, q_pos, k_pos,
                                        causal, scale, m, l, acc)
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return m, l, acc, k_nxt, v_nxt

        m, l, acc, _, _ = jax.lax.fori_loop(
            0, n_blocks, body, (m0, l0, acc0, k_blk, v_blk))
        denom = jnp.maximum(l, 1e-20).transpose(1, 0)[:, :, None]
        lse = m + jnp.log(jnp.maximum(l, 1e-20))
        # keep the two impls interchangeable: partial-merge math runs in
        # f32, but the contract is out.dtype == q.dtype
        return (acc / denom).astype(q_blk.dtype), lse

    if impl == "xla":
        @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                 out_specs=spec, check_vma=False)
        def _ring(q_blk, k_blk, v_blk):
            my_idx = jax.lax.axis_index(axis)
            return _fwd_shard(q_blk, k_blk, v_blk, my_idx)[0]

        return _ring(q, k, v)

    # -- Pallas impl: custom VJP with a backward ring -----------------------
    from .flash_attention import flash_attention_partial_bwd

    lse_spec = P(None, axis)   # [h, seq] row statistics, seq-sharded

    @jax.custom_vjp
    def _ring_pallas(q, k, v):
        return _ring_pallas_fwd(q, k, v)[0]

    def _ring_pallas_fwd(q, k, v):
        @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                 out_specs=(spec, lse_spec), check_vma=False)
        def _fwd(q_blk, k_blk, v_blk):
            my_idx = jax.lax.axis_index(axis)
            return _fwd_shard(q_blk, k_blk, v_blk, my_idx)

        out, lse = _fwd(q, k, v)
        return out, (q, k, v, out, lse)

    def _ring_pallas_bwd(res, g):
        q, k, v, out, lse = res

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(spec, spec, spec, spec, spec, lse_spec),
                 out_specs=(spec, spec, spec), check_vma=False)
        def _bwd(q_blk, k_blk, v_blk, out_blk, g_blk, lse_blk):
            my_idx = jax.lax.axis_index(axis)
            delta = jnp.einsum("shd,shd->hs", g_blk.astype(jnp.float32),
                               out_blk.astype(jnp.float32))   # [h, block]

            def body(step, carry):
                dq, k_cur, v_cur, dk_cur, dv_cur = carry
                src = jnp.mod(my_idx - step, n_blocks)
                dq_p, dk_p, dv_p = flash_attention_partial_bwd(
                    q_blk, k_cur, v_cur, g_blk, lse_blk, delta,
                    my_idx * block, src * block, causal=causal, scale=scale)
                dq = dq + dq_p
                dk_cur = dk_cur + dk_p
                dv_cur = dv_cur + dv_p
                # dk/dv accumulators TRAVEL WITH their k/v block: after a
                # full revolution they are back home carrying every
                # q-shard's contribution
                k_nxt = jax.lax.ppermute(k_cur, axis, perm)
                v_nxt = jax.lax.ppermute(v_cur, axis, perm)
                dk_nxt = jax.lax.ppermute(dk_cur, axis, perm)
                dv_nxt = jax.lax.ppermute(dv_cur, axis, perm)
                return dq, k_nxt, v_nxt, dk_nxt, dv_nxt

            dq0 = jnp.zeros(q_blk.shape, jnp.float32)
            dkv0 = jnp.zeros(k_blk.shape, jnp.float32)
            dq, _, _, dk, dv = jax.lax.fori_loop(
                0, n_blocks, body, (dq0, k_blk, v_blk, dkv0, dkv0))
            return (dq.astype(q_blk.dtype), dk.astype(k_blk.dtype),
                    dv.astype(v_blk.dtype))

        return _bwd(q, k, v, out, g, lse)

    _ring_pallas.defvjp(_ring_pallas_fwd, _ring_pallas_bwd)
    return _ring_pallas(q, k, v)


# -- serving-shaped entry points ----------------------------------------------
#
# The decode engine's chunked prefill attends 2-D operands: a chunk of
# query rows ``q [C, D]`` against the slot's gathered paged view
# ``kc/vc [T, D]`` with a traced global row offset (the prefix-causal
# mask ``t <= offset + row``). The entry points below run that exact
# computation sequence-parallel over a mesh axis — the serving face of
# the [seq, heads, dim] training kernels above. They deliberately do
# NOT reuse the flash-style running-max accumulation (`_block_attn`):
# its reduction order differs from the engine's single-softmax
# `_chunk_attention` math, and the seqpar serving contract is
# bit-identical outputs against the single-lane path.


def _prefix_chunk_attn(qh, kh, vh, rows, dh):
    """The engine's exact chunk-attention math on pre-split heads.

    ``qh [C, H, dh]``, ``kh/vh [T, H, dh]``, ``rows [C]`` global row
    positions (the causal mask bound). Mirrors
    ``models.transformer._chunk_attention`` expression-for-expression —
    one full f32 softmax per row, single P@V over the full ``T`` — so a
    per-head (or per-row-shard) slice of this computation is bitwise
    the single-device computation's slice.
    """
    T = kh.shape[0]
    scores = jnp.einsum("chd,thd->hct", qh, kh,
                        preferred_element_type=jnp.float32) / np.sqrt(dh)
    mask = (jnp.arange(T)[None, :] <= rows[:, None])[None, :, :]
    scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hct,thd->chd", probs.astype(vh.dtype), vh)


def ring_prefill_attention(q, kc, vc, n_heads: int, offset, mesh,
                           axis: str = SEQ_AXIS) -> jax.Array:
    """Ring-sharded serving chunk attention, bit-exact vs the engine.

    ``q [C, D]`` chunk rows (sharded ``P(axis, None)`` — each device
    owns ``C/n`` consecutive rows), ``kc``/``vc`` ``[T, D]`` the slot's
    gathered paged view (resharded to ``P(axis, None)`` sequence
    shards), ``offset`` the chunk's traced global base position.
    ``n - 1`` ``ppermute`` rotations reassemble the K/V shards in
    GLOBAL order on every device, then each device runs the engine's
    exact `_chunk_attention` math on its local query rows — same
    softmax, same full-``T`` contraction, hence bit-identical rows.
    Requires ``C % n == 0`` and ``T % n == 0`` (no padding: padding
    would change the reduction length and break bit-exactness).
    """
    n = int(mesh.shape[axis])
    C, D = int(q.shape[0]), int(q.shape[1])
    T = int(kc.shape[0])
    if C % n != 0:
        raise ValueError(f"chunk rows {C} must divide over {n} ring shards")
    if T % n != 0:
        raise ValueError(f"kv length {T} must divide over {n} ring shards")
    dh = D // n_heads
    perm = [(i, (i + 1) % n) for i in range(n)]

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis, None), P(axis, None), P(axis, None), P()),
             out_specs=P(axis, None), check_vma=False)
    def _ring(q_blk, k_blk, v_blk, off):
        idx = jax.lax.axis_index(axis)
        # collect every K/V shard via a static ring of rotations; after
        # j steps we hold the shard that lives at ring position
        # (idx - j) mod n
        k_parts, v_parts = [k_blk], [v_blk]
        k_cur, v_cur = k_blk, v_blk
        for _ in range(n - 1):
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)
            k_parts.append(k_cur)
            v_parts.append(v_cur)
        # global-order reassembly: shard s sits at part (idx - s) mod n
        order = jnp.mod(idx - jnp.arange(n), n)
        k_full = jnp.take(jnp.stack(k_parts), order, axis=0).reshape(T, D)
        v_full = jnp.take(jnp.stack(v_parts), order, axis=0).reshape(T, D)
        rows = off + idx * (C // n) + jnp.arange(C // n)
        out = _prefix_chunk_attn(q_blk.reshape(C // n, n_heads, dh),
                                 k_full.reshape(T, n_heads, dh),
                                 v_full.reshape(T, n_heads, dh), rows, dh)
        return out.reshape(C // n, D).astype(q_blk.dtype)

    return _ring(q, kc, vc, offset)


def reference_attention(q, k, v, causal: bool = False,
                        scale: Optional[float] = None) -> jax.Array:
    """Unsharded O(seq^2) attention — the correctness oracle for tests, and
    the local per-head computation of :func:`ops.ulysses_attention` (scores
    and softmax accumulate in f32 regardless of input dtype)."""
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        seq = q.shape[0]
        mask = jnp.tril(jnp.ones((seq, seq), bool))[None, :, :]
        scores = jnp.where(mask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs.astype(v.dtype),
                      v).astype(q.dtype)
