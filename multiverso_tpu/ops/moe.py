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
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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
