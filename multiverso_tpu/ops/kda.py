"""Kimi Delta Attention (KDA, arXiv:2510.26692): a linear-attention layer
whose memory is one matrix a head, not a row a token.

A head keeps a state ``S`` in ``R^{dk x dv}`` (float32, zero at a
sequence's start). A token with query ``q``, key ``k`` (both over
``dk``), value ``v`` (over ``dv``), per-channel decay ``a = exp(log_a)``
in ``(0, 1]^dk`` and write strength ``b`` in ``[0, 1]`` moves it by the
gated delta rule and reads it::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

which is ``S~ = Diag(a_t) S_{t-1}``, ``u_t = v_t - S~^T k_t`` (what the
memory does not yet say about ``k_t``), ``S_t = S~ + b_t k_t u_t^T``.
``b = 0, log_a = 0`` is the identity on the state: that is a padded row.

Two forms of the one recurrence:

* :func:`kda_step`: one token a slot over ``[slots, H, dk, dv]`` states,
  the serving decode step. The products against the state are
  multiply-and-sum over ``dk`` on the vector unit (a ``1 x dk`` matrix
  product a (slot, head) wastes the MXU and rounds float32 operands),
  and both are taken against the OLD state (``S_t^T q = S~^T q + (b k .
  q) u``), so that one pass over it feeds both and a second writes the
  new one.
* :func:`kda_chunk`: ``T`` tokens of ONE sequence from a start state,
  blocks of :data:`BLOCK` positions. With ``G_t`` the cumulative
  ``log_a`` inside a block, the block's pseudo-values solve the unit
  lower-triangular system ``(I + A Diag(b)) U = V - (K o e^G) S_0``,
  ``A[t, j] = sum_c k_t[c] k_j[c] e^(G_t[c] - G_j[c])`` for ``j < t``
  (the WY form of the delta rule); outputs and the next block's state
  are matrix products of ``W = Diag(b) U``. Everything that does not
  depend on the state (``A``, its inverse, the query-key block) is made
  for all blocks at once; the scan over blocks carries the state by five
  matrix products.

**The exponent is never split.** ``e^(G_t - G_j)`` is formed only for
``t >= j``, as the exponential of the non-positive difference, by a sum
over ``dk`` and not as a product of ``e^(G_t)`` and ``e^(-G_j)``: at the
gate's bound of -5 a position the second factor overflows float32 after
17 positions.

The convolution in front of q, k and v (:func:`short_conv_chunk`,
:func:`short_conv_step`) is depthwise and causal over ``taps`` positions;
a sequence's tail is its last ``taps - 1`` input rows.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# positions to a block of the chunkwise form: the in-block system is
# BLOCK x BLOCK a head, the state is carried T / BLOCK times
BLOCK = 64
_HI = jax.lax.Precision.HIGHEST


def short_conv_chunk(x: jax.Array, taps: jax.Array, tail: jax.Array,
                     length) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution of a chunk ``x`` [T, C] that
    continues a sequence whose last ``K - 1`` input rows are ``tail``
    [K - 1, C] (zeros at a sequence's start): ``y_t = sum_j taps[j]
    x_(t - K + 1 + j)``, float32. Returns ``(y [T, C], the tail after
    the chunk's first ``length`` rows)``; rows past ``length`` are
    padding and reach neither."""
    K = taps.shape[0]
    T = x.shape[0]
    xx = jnp.concatenate([tail.astype(x.dtype), x], axis=0)     # [T + K - 1]
    y = sum(taps[j].astype(jnp.float32) * xx[j:j + T].astype(jnp.float32)
            for j in range(K))
    return y, jax.lax.dynamic_slice_in_dim(xx, length, K - 1, axis=0)


def short_conv_step(x: jax.Array, taps: jax.Array, tail: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """One token a slot of the same convolution: ``x`` [S, C], ``tail``
    [S, K - 1, C]. Returns ``(y [S, C] float32, tail [S, K - 1, C])``
    with ``x`` shifted in."""
    K = taps.shape[0]
    f32 = jnp.float32
    y = taps[K - 1].astype(f32) * x.astype(f32) + sum(
        taps[j].astype(f32) * tail[:, j].astype(f32) for j in range(K - 1))
    return y, jnp.concatenate([tail[:, 1:], x[:, None].astype(tail.dtype)],
                              axis=1)


def kda_step(q: jax.Array, k: jax.Array, v: jax.Array, log_a: jax.Array,
             b: jax.Array, state: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """One token a slot: ``q``, ``k``, ``log_a`` [S, H, dk], ``v``
    [S, H, dv], ``b`` [S, H], ``state`` [S, H, dk, dv] float32. Returns
    ``(o [S, H, dv] float32, state')``."""
    f32 = jnp.float32
    q, k, v, b = (x.astype(f32) for x in (q, k, v, b))
    s1 = state * jnp.exp(log_a.astype(f32))[..., None]
    # both products against the OLD state, so that one pass over it
    # feeds them: S'^T q = S~^T q + (b k . q) u
    u = v - jnp.sum(k[..., None] * s1, axis=2)
    o = jnp.sum(q[..., None] * s1, axis=2) \
        + jnp.sum(b[..., None] * k * q, axis=-1, keepdims=True) * u
    return o, s1 + (b[..., None] * k)[..., None] * u[:, :, None, :]


# -- the step in place over a pool of states: a Pallas TPU kernel ------------
# heads to a grid step: 16 x 64 KiB of state in and out, double-buffered
_STEP_HEADS = 16


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def step_kernel_applies(heads: int, dk: int, dv: int) -> bool:
    """Whether a decode step's KDA layers take :func:`kda_step_pool`'s
    kernel: decided from what the program can observe where it is jitted
    (backend, the state's shape). A head's float32 state of ``dk % 8 ==
    0`` rows by ``dv % 128 == 0`` lanes is whole ``(8, 128)`` tiles."""
    return (_on_tpu() and dk % 8 == 0 and dv % 128 == 0
            and heads % min(_STEP_HEADS, heads) == 0)


def pool_step(heads: int, dk: int, dv: int) -> Optional[Callable]:
    """What a model's program builder hands its decode step as
    ``kda_pool_step``: :func:`kda_step_pool` where
    :func:`step_kernel_applies`, else None (the step runs
    :func:`kda_step` on the layer's slab and writes it back)."""
    return kda_step_pool if step_kernel_applies(heads, dk, dv) else None


def _step_kernel(cols_ref, v_ref, s_ref, o_ref, s_out_ref, *, hb: int):
    """One slot, ``hb`` heads. ``cols_ref`` [dk, 5 hb]: lane ``n hb + i``
    is head ``i``'s vector ``n`` of (a, k, b k, q, active) with ``dk``
    along the sublanes, as the state's rows lie; ``v_ref`` / ``o_ref``
    [hb, dv]; the states [hb, dk, dv]."""
    cols = cols_ref[...]
    for i in range(hb):
        col = lambda n: cols[:, n * hb + i:n * hb + i + 1]      # [dk, 1]
        a, k, kb, q, act = (col(n) for n in range(5))
        old = s_ref[i]
        s1 = old * a
        u = v_ref[i:i + 1, :] - jnp.sum(k * s1, axis=0, keepdims=True)
        o_ref[i:i + 1, :] = jnp.sum(q * s1, axis=0, keepdims=True) \
            + jnp.sum(q * kb, axis=0, keepdims=True) * u
        s_out_ref[i] = jnp.where(act > 0, s1 + kb * u, old)


def kda_step_pool(q: jax.Array, k: jax.Array, v: jax.Array,
                  log_a: jax.Array, b: jax.Array, active: jax.Array,
                  pool: jax.Array, layer: int,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """:func:`kda_step` of layer ``layer`` IN PLACE over the whole pool
    of states ``[layers, S, H, dk, dv]`` float32: every state of the
    layer is read once and written once (the pool is aliased to the
    result; the other layers' states are never touched), both products
    and the write in one pass over a head's 64 KiB. A slot that is not
    ``active`` [S] keeps its state bit for bit. ``q``, ``k``, ``log_a``
    [S, H, dk], ``v`` [S, H, dv], ``b`` [S, H]. Returns ``(o [S, H, dv]
    float32, pool)``."""
    if interpret is None:
        interpret = not _on_tpu()
    return _kda_step_pool(q, k, v, log_a, b, active, pool, layer=int(layer),
                          interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def _kda_step_pool(q, k, v, log_a, b, active, pool, *, layer, interpret):
    f32 = jnp.float32
    _, S, H, dk, dv = pool.shape
    hb = min(_STEP_HEADS, H)
    q, k, v, b = (x.astype(f32) for x in (q, k, v, b))
    on = active[:, None, None]
    vectors = jnp.stack([
        jnp.where(on, jnp.exp(log_a.astype(f32)), 1.0),
        k, jnp.where(on, b[..., None] * k, 0.0), q,
        jnp.broadcast_to(on.astype(f32), k.shape)], axis=1)   # [S, 5, H, dk]
    # [S, H / hb, dk, 5 hb]: a grid step's columns, dk on the sublanes
    cols = vectors.reshape(S, 5, H // hb, hb, dk).transpose(0, 2, 4, 1, 3) \
        .reshape(S, H // hb, dk, 5 * hb)
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb),
        grid=(S, H // hb),
        in_specs=[
            pl.BlockSpec((None, None, dk, 5 * hb), lambda s, h: (s, h, 0, 0)),
            pl.BlockSpec((None, hb, dv), lambda s, h: (s, h, 0)),
            pl.BlockSpec((None, None, hb, dk, dv),
                         lambda s, h: (layer, s, h, 0, 0))],
        out_specs=[
            pl.BlockSpec((None, hb, dv), lambda s, h: (s, h, 0)),
            pl.BlockSpec((None, None, hb, dk, dv),
                         lambda s, h: (layer, s, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((S, H, dv), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="kda_step_pool",
    )(cols, v, pool)
    return o, pool


def _blocks(x, n: int, c: int):
    """``[n * c, H, ...]`` -> ``[n, H, c, ...]``."""
    return jnp.moveaxis(x.reshape((n, c) + x.shape[1:]), 2, 1)


def kda_chunk(q: jax.Array, k: jax.Array, v: jax.Array, log_a: jax.Array,
              b: jax.Array, state: jax.Array,
              valid: Optional[jax.Array] = None, block: int = BLOCK
              ) -> Tuple[jax.Array, jax.Array]:
    """``T`` tokens of one sequence, chunkwise: ``q``, ``k``, ``log_a``
    [T, H, dk], ``v`` [T, H, dv], ``b`` [T, H], ``state`` [H, dk, dv]
    float32 (the sequence's state before the first token), ``valid``
    [T] bool (None: all). Rows that are not valid leave the state as it
    is and their outputs mean nothing. Returns ``(o [T, H, dv] float32,
    the state after the last token)``."""
    f32 = jnp.float32
    T, H, dk = q.shape
    q, k, v, g, b = (x.astype(f32) for x in (q, k, v, log_a, b))
    if valid is not None:
        g = jnp.where(valid[:, None, None], g, 0.0)
        b = jnp.where(valid[:, None], b, 0.0)
    c = min(block, T)
    n = -(-T // c)
    if n * c > T:       # whole blocks: the rows added are identities
        grow = lambda x: jnp.pad(x, [(0, n * c - T)] + [(0, 0)] * (x.ndim - 1))
        q, k, v, g, b = (grow(x) for x in (q, k, v, g, b))
    q, k, v, g = (_blocks(x, n, c) for x in (q, k, v, g))     # [n, H, c, .]
    b = _blocks(b, n, c)                                      # [n, H, c]
    G = jnp.cumsum(g, axis=2)
    # e^(G_t - G_j) for t >= j only, the exponent whole (module docstring)
    row, col = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.minimum(G[:, :, :, None, :] - G[:, :, None, :, :],
                                0.0))                     # [n, H, t, j, dk]
    kk = jnp.sum(k[:, :, :, None, :] * k[:, :, None, :, :] * decay, axis=-1)
    qk = jnp.sum(q[:, :, :, None, :] * k[:, :, None, :, :] * decay, axis=-1)
    A = jnp.where(row > col, kk, 0.0) * b[:, :, None, :]
    B = jnp.where(row >= col, qk, 0.0)
    # (I + A)^-1 of every block and head at once: unit lower triangular
    inv = jax.lax.linalg.triangular_solve(
        A + jnp.eye(c, dtype=f32), jnp.broadcast_to(jnp.eye(c, dtype=f32),
                                                    A.shape),
        left_side=True, lower=True, unit_diagonal=True)
    grown = jnp.exp(G)                                    # e^(G_t), <= 1
    k_in, q_in = k * grown, q * grown
    k_out = k * jnp.exp(G[:, :, -1:, :] - G)              # to the block's end
    end = grown[:, :, -1, :]                              # [n, H, dk]

    def one(S, blk):
        inv, B, k_in, q_in, k_out, end, v, b = blk
        mm = lambda eq, x, y: jnp.einsum(eq, x, y, precision=_HI)
        u = mm("htj,hjv->htv", inv, v - mm("htc,hcv->htv", k_in, S))
        w = b[..., None] * u
        o = mm("htc,hcv->htv", q_in, S) + mm("htj,hjv->htv", B, w)
        return end[..., None] * S + mm("hjc,hjv->hcv", k_out, w), o

    state, o = jax.lax.scan(one, state.astype(f32),
                            (inv, B, k_in, q_in, k_out, end, v, b))
    return jnp.moveaxis(o, 1, 2).reshape(n * c, H, -1)[:T], state
