"""Mixture-of-experts layer with expert parallelism over an ``expert`` axis.

The reference has no expert parallelism (SURVEY §2.5). As with pipeline
parallelism, the TPU-first mesh design makes it a natural extension of the
framework's model-parallel substrate: expert weights are sharded over an
``expert`` mesh axis exactly like parameter-table shards over the ``server``
axis, and token routing is two ``lax.all_to_all`` collectives over ICI (the
canonical Switch-Transformer dispatch):

  1. top-1 gating with capacity ``C`` builds one-hot dispatch/combine tensors
     (tokens over capacity are dropped — their combine weight is zero);
  2. tokens are packed into per-expert buffers ``[E, C, d]`` and exchanged
     with ``all_to_all`` so each device holds ``[E/S, S*C, d]`` for its local
     experts;
  3. local experts run as a ``vmap`` over the expert dim (big batched matmuls
     on the MXU);
  4. the reverse ``all_to_all`` returns expert outputs, combined with the
     gate weights.

Everything is expressed with einsums over one-hot tensors, so the layer is
differentiable end-to-end (gate weights carry the gradient through routing).

The second half of the module is the serving-side expert layer of a chip
that holds a SHARE of a layer's experts (:func:`route_topk` or
:func:`route_group_limited`, then :func:`held_expert_layer`): top-k
routing over every router output with no capacity and no dropped token
(a softmax with unnormalised gates, or sigmoid scores limited to a
token's best groups of experts with normalised gates), identity
("zero-compute") experts, and the part of the layer's result that the
held experts give. It runs without an exchange: what the absent experts
would add is left out. Over a decode step's rows every held expert runs
over every row under a gate mask (reading the weights sets the time,
whatever the routing); over more than :data:`GROUPED_MIN_ROWS` rows (a
prefill chunk) each held expert runs over only the rows that picked it:
a grouped product over the (row, held expert) pairs sorted by expert.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental.pallas.ops.tpu.megablox import gmm
from jax.sharding import PartitionSpec as P

EXPERT_AXIS = "expert"


def top1_gating(logits: jax.Array, capacity: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Switch-style top-1 gating.

    Args:
      logits: ``[T, E]`` router logits for T tokens over E experts.
      capacity: per-expert token budget C.

    Returns ``(dispatch, combine, aux_loss)`` where ``dispatch`` is a
    ``[T, E, C]`` 0/1 routing tensor, ``combine = dispatch * gate`` carries
    the gate probabilities, and ``aux_loss`` is the load-balancing loss
    (mean over experts of fraction-routed x mean-gate x E^2, the Switch
    formulation).
    """
    n_tokens, n_experts = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)                     # [T, E]
    expert_idx = jnp.argmax(gates, axis=-1)                     # [T]
    # Buffer positions are counters: keep them int32 regardless of the
    # logits dtype — a bf16 cumsum loses integer exactness past 256 tokens
    # and would pack multiple tokens into one slot.
    onehot_i = jax.nn.one_hot(expert_idx, n_experts,
                              dtype=jnp.int32)                  # [T, E]
    onehot = onehot_i.astype(logits.dtype)
    # Position of each token within its expert's buffer (0-based).
    position = jnp.cumsum(onehot_i, axis=0) * onehot_i - onehot_i  # [T, E]
    keep = ((position < capacity) & (onehot_i > 0)).astype(
        logits.dtype)                                           # [T, E]
    dispatch = keep[:, :, None] * jax.nn.one_hot(
        position, capacity, dtype=logits.dtype)                 # [T, E, C]
    gate_val = jnp.sum(gates * onehot, axis=-1)                 # [T]
    combine = dispatch * gate_val[:, None, None]                # [T, E, C]
    frac_routed = jnp.mean(onehot, axis=0)                      # [E]
    mean_gate = jnp.mean(gates, axis=0)                         # [E]
    aux = jnp.sum(frac_routed * mean_gate) * n_experts
    return dispatch, combine, aux


def moe_apply(
    expert_fn: Callable[[Any, jax.Array], jax.Array],
    expert_params: Any,
    router_w: jax.Array,
    x: jax.Array,
    mesh,
    axis: str = EXPERT_AXIS,
    capacity_factor: float = 2.0,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE layer.

    Args:
      expert_fn: ``(one_expert_params, tokens[c, d]) -> tokens[c, d]``.
      expert_params: pytree with leading dim ``E`` on every leaf, sharded
        over ``axis``.
      router_w: ``[d, E]`` router weights (replicated).
      x: ``[T, d]`` tokens, sharded over ``axis`` on dim 0 (data-parallel
        token groups).
      mesh: mesh containing ``axis`` of size S; requires ``E % S == 0`` and
        ``T % S == 0``.
      capacity_factor: per-expert buffer = ``ceil(cf * T_local / E)``.

    Returns ``(y, aux_loss)`` with ``y`` sharded like ``x``.
    """
    n_shards = int(mesh.shape[axis])
    n_experts = int(router_w.shape[-1])
    if n_experts % n_shards != 0:
        raise ValueError(f"E={n_experts} not divisible by mesh axis "
                         f"{axis}={n_shards}")
    if int(x.shape[0]) % n_shards != 0:
        raise ValueError(f"token count T={int(x.shape[0])} not divisible by "
                         f"mesh axis {axis}={n_shards}")
    tokens_local = int(x.shape[0]) // n_shards
    capacity = int(np.ceil(capacity_factor * tokens_local / n_experts))

    param_spec = jax.tree.map(
        lambda leaf: P(axis, *(None,) * (np.ndim(leaf) - 1)), expert_params)
    x_spec = P(axis, *(None,) * (x.ndim - 1))

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(param_spec, P(), x_spec),
             out_specs=(x_spec, P()),
             check_vma=False)
    def _moe(p_local, rw, x_local):
        logits = x_local @ rw                                   # [t, E]
        dispatch, combine, aux = top1_gating(logits, capacity)
        # Pack per-expert send buffers, then exchange: each device ends up
        # with the [E/S local experts, S*C tokens, d] it is responsible for.
        buf = jnp.einsum("tec,td->ecd", dispatch, x_local)      # [E, C, d]
        buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=1,
                                 tiled=True)                    # [E/S, S*C, d]
        out = jax.vmap(expert_fn)(p_local, buf)                 # [E/S, S*C, d]
        out = jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                                 tiled=True)                    # [E, C, d]
        y = jnp.einsum("tec,ecd->td", combine, out)             # [t, d]
        return y, jax.lax.pmean(aux, axis)

    return _moe(expert_params, router_w, x)


def mlp_expert(params: Any, tokens: jax.Array) -> jax.Array:
    """Default expert: 2-layer GELU MLP ``{w1: [d, h], w2: [h, d]}``."""
    h = jax.nn.gelu(tokens @ params["w1"])
    return h @ params["w2"]


def init_moe_params(rng: np.random.Generator, n_experts: int, d_model: int,
                    d_hidden: int, dtype=jnp.float32):
    """Random router + stacked expert MLP params (numpy rng for portability)."""
    scale_in = 1.0 / np.sqrt(d_model)
    scale_hid = 1.0 / np.sqrt(d_hidden)
    router_w = jnp.asarray(
        rng.standard_normal((d_model, n_experts)) * scale_in, dtype)
    expert_params = {
        "w1": jnp.asarray(
            rng.standard_normal((n_experts, d_model, d_hidden)) * scale_in,
            dtype),
        "w2": jnp.asarray(
            rng.standard_normal((n_experts, d_hidden, d_model)) * scale_hid,
            dtype),
    }
    return router_w, expert_params


# -- serving: one chip's share of a top-k expert layer -------------------------
# ``held_expert_layer``'s counts: this many scalars, then a load a held expert
COUNT_SCALARS = 6

# Rows above which the held experts run grouped. Dense, a held expert
# runs over every row: a bfloat16 weight does 2T FLOPs for its 2 bytes,
# so over T rows the products cost no more than reading the weights
# while T <= 197 TFLOP/s / 819 GB/s ~ 240 (v5e). Above that they are
# bound by the matrix units doing work routing never asked for, and the
# grouped form, bound by the weights' read, is the cheaper: a decode
# step (one row a slot, 128) stays dense, a 512-row prefill chunk is
# grouped.
GROUPED_MIN_ROWS = 256
# the grouped product's row tile: the MXU's 128 rows, at which a held
# expert's tile does 128 FLOPs a weight byte, under the ridge's ~240
_GROUP_ROWS = 128
# the most bytes of a weight tile and of a float32 output tile: two of
# the first, three of the second (double-buffered, and the accumulator)
# and two row tiles come to ~13 MiB, inside the 16 MiB of VMEM a kernel
# may use by default
_WEIGHT_TILE_BYTES = 4 << 20
_OUT_TILE_BYTES = 5 << 18


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _tile(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``cap`` (``dim`` itself where 128 does not divide it: a whole
    dimension is always a legal block)."""
    if dim % 128:
        return dim
    lanes = dim // 128
    return 128 * max(d for d in range(1, lanes + 1)
                     if lanes % d == 0 and 128 * d <= max(cap, 128))


def grouped_tiling(k: int, n: int) -> Tuple[int, int, int]:
    """``(tm, tk, tn)`` of a grouped product ``[m, k] x [groups, k, n]``:
    the output tile as wide as fits (each lhs tile is read once an
    output column tile), then the weight tile as deep as fits, so that a
    grid step moves ~4 MiB of weights against its ~0.35 us of
    overhead."""
    tn = _tile(n, _OUT_TILE_BYTES // (_GROUP_ROWS * 4))
    return _GROUP_ROWS, _tile(k, _WEIGHT_TILE_BYTES // (tn * 2)), tn


def grouped_dot(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array
                ) -> jax.Array:
    """``lhs`` [m, k] rows sorted by group, ``group_sizes[e]`` of them
    against ``rhs[e]`` [k, n], accumulated in float32: [m, n] float32.
    A group's rows are computed in ``_GROUP_ROWS``-row tiles, each group
    reading its weights once; rows past ``sum(group_sizes)`` are NEVER
    WRITTEN (read them through a select, never a product: they may hold
    NaN). ``megablox.gmm``, interpreted off the chip."""
    return gmm(lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
               tiling=grouped_tiling(lhs.shape[1], rhs.shape[2]),
               interpret=not _on_tpu())


def grouped_rows(group_sizes: jax.Array) -> jax.Array:
    """The rows :func:`grouped_dot` computes for ``group_sizes``: each
    non-empty group's row span, rounded out to whole tiles (a tile two
    groups share is computed once for each)."""
    tm = _GROUP_ROWS
    ends = jnp.cumsum(group_sizes)
    tiles = (ends + tm - 1) // tm - (ends - group_sizes) // tm
    return tm * jnp.sum(jnp.where(group_sizes > 0, tiles, 0))


def route_topk(u: jax.Array, router_w: jax.Array, router_bias: jax.Array,
               top_k: int, scale: float, normalize: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    """Softmax router over ALL of the layer's outputs, in float32.

    ``p = softmax(float32(u) @ router_w)``; the ``top_k`` largest of
    ``p + router_bias`` are chosen (the bias moves the choice, never the
    gate); a chosen output's gate is ``scale * p``, not renormalised,
    or with ``normalize`` ``scale * p / sum(p of the picks)`` (a softmax
    over the picks). Returns ``(idx [T, k] int32, gates [T, k]
    float32)``. No capacity: every token keeps all of its picks."""
    logits = jnp.dot(u.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(p + router_bias.astype(jnp.float32), top_k)
    gates = jnp.take_along_axis(p, idx, axis=-1)
    if normalize:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), scale * gates


def route_group_limited(u: jax.Array, router_w: jax.Array,
                        router_bias: jax.Array, top_k: int, n_group: int,
                        topk_group: int, scale: float
                        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sigmoid router with a group limit (DeepSeek-V3's ``noaux_tc``), in
    float32.

    ``s = sigmoid(float32(u) @ router_w)`` and ``b = s + router_bias``;
    the outputs lie in ``n_group`` groups of consecutive experts; a
    group's score is the sum of its two largest ``b``; the token keeps
    its ``topk_group`` best groups and picks the ``top_k`` largest ``b``
    inside them (the bias moves the choice, never the gate). A pick's
    gate is its ``s``, divided by the picks' sum (+ 1e-20), times
    ``scale``. Outputs outside the kept groups are
    masked with -inf (the published code fills them with 0.0, which is
    the same choice wherever ``top_k`` kept outputs have ``b > 0``).
    Returns ``(idx [T, k] int32, gates [T, k] float32, kept [T, n_group]
    bool)``. No capacity: every token keeps all of its picks."""
    logits = jnp.dot(u.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    T, E = s.shape
    b = (s + router_bias.astype(jnp.float32)).reshape(T, n_group,
                                                      E // n_group)
    group_score = jnp.sum(jax.lax.top_k(b, 2)[0], axis=-1)      # [T, G]
    _, best = jax.lax.top_k(group_score, topk_group)
    kept = jnp.any(jax.nn.one_hot(best, n_group, dtype=jnp.bool_), axis=1)
    _, idx = jax.lax.top_k(
        jnp.where(kept[..., None], b, -jnp.inf).reshape(T, E), top_k)
    gates = jnp.take_along_axis(s, idx, axis=-1)
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), scale * gates, kept


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    """``(silu(x w_gate) * (x w_up)) w_down``, float32 accumulation,
    result in float32."""
    f32 = jnp.float32
    g = jnp.dot(x, w_gate, preferred_element_type=f32)
    v = jnp.dot(x, w_up, preferred_element_type=f32)
    h = (jax.nn.silu(g) * v).astype(x.dtype)
    return jnp.dot(h, w_down, preferred_element_type=f32)


def _held_dense(u, gate_held, experts):
    """Every held expert over every row, its output times the row's gate
    ``gate_held`` [T, E] (exact zero where the row did not pick it):
    [T, D] float32."""
    f32 = jnp.float32
    g = jnp.einsum("td,edf->etf", u, experts["w_gate"],
                   preferred_element_type=f32)
    v = jnp.einsum("td,edf->etf", u, experts["w_up"],
                   preferred_element_type=f32)
    h = jax.nn.silu(g) * v * gate_held.T[:, :, None]
    return jnp.einsum("etf,efd->td", h.astype(u.dtype), experts["w_down"],
                      preferred_element_type=f32)


def _held_grouped(u, local, take, gates, experts, sizes):
    """Each held expert over the rows that picked it: the (row, pick)
    pairs that ``take`` marks (``sizes`` [E] of them an expert), sorted
    by expert, through three grouped products, each pair's output added
    back to its row in float32. Per pair the arithmetic is the dense
    form's (``g``, ``v`` float32 from bfloat16 operands, ``silu(g) v
    gate`` in float32, rounded to bfloat16, the down product accumulated
    in float32); only the order of the sum over a row's experts differs.
    Returns ``[T, D]`` float32."""
    T, k = local.shape
    E = experts["w_gate"].shape[0]
    pairs = T * k
    m = -(-pairs // _GROUP_ROWS) * _GROUP_ROWS
    # a pair not taken (and a pad) gets the key E: no group, sorted last
    key = jnp.pad(jnp.where(take, local, E).reshape(-1), (0, m - pairs),
                  constant_values=E)
    order = jnp.argsort(key, stable=True)
    x = jnp.take(u, order // k, axis=0, mode="clip")
    gate = jnp.take(gates.reshape(-1), order, mode="clip")
    g = grouped_dot(x, experts["w_gate"], sizes)
    v = grouped_dot(x, experts["w_up"], sizes)
    h = (jax.nn.silu(g) * v * gate[:, None]).astype(u.dtype)
    out = grouped_dot(h, experts["w_down"], sizes)
    # back to the rows, one tile of sorted pairs at a time and only the
    # tiles a group wrote: a row past the groups (never written, maybe
    # NaN) passes through the select, never a product
    served = jnp.sum(sizes)
    rows = jnp.minimum(order // k, T - 1)

    def add_tile(i, y):
        at = i * _GROUP_ROWS
        tile = jax.lax.dynamic_slice_in_dim(out, at, _GROUP_ROWS)
        live = at + jnp.arange(_GROUP_ROWS) < served
        return y.at[jax.lax.dynamic_slice_in_dim(rows, at, _GROUP_ROWS)].add(
            jnp.where(live[:, None], tile, 0.0))

    return jax.lax.fori_loop(0, -(-served // _GROUP_ROWS), add_tile,
                             jnp.zeros(u.shape, jnp.float32))


def held_expert_layer(u: jax.Array, idx: jax.Array, gates: jax.Array,
                      experts: Any, n_ffn_experts: int, expert_offset: int,
                      identity: bool = True, valid: jax.Array | None = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """This chip's part of ``sum_i g_i Expert_i(u)`` for tokens ``u``
    [T, D] routed by :func:`route_topk`.

    Router outputs ``< n_ffn_experts`` are SwiGLU experts, of which this
    chip holds ``experts["w_gate"].shape[0]`` starting at
    ``expert_offset`` (``experts``: ``w_gate``/``w_up`` [E, D, F],
    ``w_down`` [E, F, D]); outputs ``>= n_ffn_experts`` are identity
    experts, which hold no weights and are applied where the token lives
    (``identity=False`` leaves them to another share, so that shares can
    be summed with the identity part counted once). Picks of absent
    experts add nothing. No capacity: no pick is ever dropped.

    The form follows the row count, a static shape. Up to
    :data:`GROUPED_MIN_ROWS` rows (a decode step) EVERY held expert runs
    over EVERY row, its output times the row's gate (exact zero where
    the row did not pick it): one batched product a matrix, bound by
    reading the held experts' weights whatever the routing. Over more (a
    prefill chunk, where the dense form would pay ``E x T`` rows for the
    ``~T k E / outputs`` routing sends) each held expert runs over the
    rows that picked it (:func:`grouped_dot`); rows that are not
    ``valid`` are given no group there, and their result is the identity
    part alone.

    Returns ``(y [T, D] float32, counts [COUNT_SCALARS + E] float32)``:
    over the ``valid`` tokens, their number, their picks of FFN experts,
    of identity experts, of HELD experts, the rows the grouped products
    computed (tile-padded; 0 dense) and the held pairs they served (0
    dense), then each held expert's load."""
    f32 = jnp.float32
    E = experts["w_gate"].shape[0]
    T = u.shape[0]
    local = idx - expert_offset                                   # [T, k]
    held = (local >= 0) & (local < E) & (idx < n_ffn_experts)
    live = (jnp.ones((T,), f32) if valid is None else valid.astype(f32))
    if T > GROUPED_MIN_ROWS:
        # a pick at a gate of 0 adds exactly 0: it is given no group
        take = held & (gates > 0)
        if valid is not None:
            take = take & valid[:, None]
        load = jnp.sum(jax.nn.one_hot(jnp.where(take, local, E), E,
                                      dtype=f32), axis=(0, 1))
        sizes = load.astype(jnp.int32)
        y = _held_grouped(u, local, take, gates, experts, sizes)
        grouped = jnp.stack([grouped_rows(sizes).astype(f32), jnp.sum(load)])
    else:
        # [T, E] gate of each held expert for each token (0 = not picked)
        gate_held = jnp.sum(
            jnp.where(held[..., None],
                      jax.nn.one_hot(local, E, dtype=f32) * gates[..., None],
                      0.0), axis=1)
        y = _held_dense(u, gate_held, experts)
        load = jnp.sum(live[:, None] * (gate_held > 0), axis=0)
        grouped = jnp.zeros((2,), f32)
    is_id = idx >= n_ffn_experts
    if identity:
        y = y + jnp.sum(jnp.where(is_id, gates, 0.0), axis=-1,
                        keepdims=True) * u.astype(f32)
    counts = jnp.concatenate([
        jnp.stack([jnp.sum(live),
                   jnp.sum(live[:, None] * (~is_id)),
                   jnp.sum(live[:, None] * is_id),
                   jnp.sum(live[:, None] * held)]),
        grouped, load])
    return y, counts
