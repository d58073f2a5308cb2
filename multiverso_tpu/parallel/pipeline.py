"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

The reference framework has no pipeline parallelism (SURVEY §2.5 — the word
"pipeline" there means compute/comm double-buffering: ``ASyncBuffer``
``include/multiverso/util/async_buffer.h:11``, LogReg ``GetPipelineTable``
``Applications/LogisticRegression/src/model/ps_model.cpp:236``). Our TPU-first
design generalises the reference's storage-only model parallelism to real
compute parallelism, and pipeline parallelism falls out of the mesh design:

* stages are devices along a ``stage`` mesh axis;
* activations flow stage -> stage over ICI via ``lax.ppermute``;
* the GPipe microbatch schedule is a ``lax.scan`` inside ``shard_map`` —
  tick ``t`` has stage ``s`` working on microbatch ``t - s`` (bubble at the
  ramp-up/ramp-down edges);
* the whole schedule is differentiable end-to-end: the transpose of
  ``ppermute`` is the reverse ring, so reverse-mode AD derives the backward
  pipeline schedule automatically.

Constraints (the usual SPMD pipeline contract): every stage has the same
activation shape and the same ``stage_fn`` signature; per-stage parameters are
stacked on a leading ``n_stages`` dim and sharded over the ``stage`` axis.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

STAGE_AXIS = "stage"


def make_pipeline_mesh(n_stages: Optional[int] = None,
                       devices: Optional[Sequence] = None):
    """A 1-D mesh whose single axis is the pipeline ``stage`` axis."""
    from ..topology import make_mesh

    if devices is None:
        devices = jax.devices()
    if n_stages is None:
        n_stages = len(devices)
    return make_mesh((n_stages,), axis_names=(STAGE_AXIS,),
                     devices=devices[:n_stages])


def stack_stage_params(per_stage_params: Sequence[Any]):
    """Stack a list of per-stage parameter pytrees on a leading stage dim."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage_params)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    params: Any,
    xs: jax.Array,
    mesh,
    axis: str = STAGE_AXIS,
) -> jax.Array:
    """Apply ``f_{S-1}(...f_1(f_0(x)))`` pipelined over mesh axis ``axis``.

    Args:
      stage_fn: ``(stage_params, activation) -> activation``; activation
        shape must be invariant across stages.
      params: pytree whose leaves have leading dim ``n_stages``; sharded (or
        shardable) over ``axis``.
      xs: ``[n_micro, micro_batch, ...]`` microbatched input (replicated).
      mesh: mesh containing ``axis``.

    Returns ``[n_micro, micro_batch, ...]`` outputs, replicated across the
    stage axis. Differentiable in ``params`` and ``xs``.
    """
    n_stages = int(mesh.shape[axis])
    n_micro = int(xs.shape[0])
    for leaf in jax.tree.leaves(params):
        if np.ndim(leaf) == 0 or np.shape(leaf)[0] != n_stages:
            raise ValueError(
                f"params leaf has leading dim "
                f"{np.shape(leaf)[0] if np.ndim(leaf) else 'none (scalar)'} "
                f"!= mesh axis {axis}={n_stages}; stack exactly one param "
                f"set per stage")
    param_spec = jax.tree.map(
        lambda leaf: P(axis, *(None,) * (np.ndim(leaf) - 1)), params)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(param_spec, P()), out_specs=P(),
             check_vma=False)
    def _pipelined(p_shard, xs_rep):
        stage = jax.lax.axis_index(axis)
        p_local = jax.tree.map(lambda leaf: leaf[0], p_shard)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        state0 = jnp.zeros_like(xs_rep[0])
        out0 = jnp.zeros_like(xs_rep)

        def tick(carry, t):
            state, outputs = carry
            # Stage 0 feeds microbatch t (clamped; garbage after the last
            # microbatch never survives long enough to be recorded).
            feed = jax.lax.dynamic_index_in_dim(
                xs_rep, jnp.clip(t, 0, n_micro - 1), keepdims=False)
            inp = jnp.where(stage == 0, feed, state)
            out = stage_fn(p_local, inp)
            # The last stage records microbatch t-(n_stages-1) at tick t.
            rec = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            recorded = jax.lax.dynamic_update_index_in_dim(
                outputs, out.astype(outputs.dtype), rec, axis=0)
            take = jnp.logical_and(stage == n_stages - 1,
                                   t >= n_stages - 1)
            outputs = jnp.where(take, recorded, outputs)
            state = jax.lax.ppermute(out, axis, perm)
            return (state, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (state0, out0), jnp.arange(n_micro + n_stages - 1))
        # Outputs are only valid on the last stage; a masked psum replicates
        # them (and its transpose routes cotangents back in the bwd pass).
        mask = (stage == n_stages - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, axis)

    return _pipelined(params, xs)


def microbatch(batch: jax.Array, n_micro: int) -> jax.Array:
    """Split ``[B, ...]`` into ``[n_micro, B//n_micro, ...]``."""
    if batch.shape[0] % n_micro != 0:
        raise ValueError(
            f"batch dim {batch.shape[0]} not divisible by n_micro={n_micro}")
    return batch.reshape((n_micro, batch.shape[0] // n_micro) + batch.shape[1:])


def pipeline_value_and_grad(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
    params: Any,
    xs: jax.Array,
    aux: jax.Array,
    mesh,
    axis: str = STAGE_AXIS,
):
    """1F1B pipelined training step: ``(mean loss, param grads)``.

    :func:`pipeline_apply` + reverse-mode AD yields the GPipe schedule —
    all forwards, then all backwards — whose activation residency grows
    with ``n_micro`` (every microbatch's residuals live until its
    backward). This hand-scheduled 1F1B form caps residency at
    ``O(n_stages)`` instead: each tick runs ONE forward slot and ONE
    backward slot per stage, activations ``ppermute`` down the ring while
    cotangents ``ppermute`` up it, and a stage stashes only the INPUT of
    each in-flight microbatch (2*n_stages ring slots), recomputing the
    stage forward inside the backward slot (standard 1F1B-with-remat: one
    extra forward per microbatch buys n_micro-independent memory).

    Schedule (stage ``s``, tick ``t``): forward slot runs microbatch
    ``m_f = t - s``; backward slot runs ``m_b = t - (2S - 1 - s)`` — the
    last stage turns a microbatch around one tick after finishing its
    forward, and backwards cascade stage-by-stage in reverse. The scan
    runs ``n_micro + 2*n_stages - 1`` ticks (the last, inclusive tick is
    stage 0's backward of the final microbatch at
    ``t = n_micro + 2*n_stages - 2``); for ``n_micro >> n_stages``
    total compute matches GPipe + one remat forward.

    Args:
      stage_fn: ``(stage_params, activation) -> activation`` (shape
        invariant across stages).
      loss_fn: ``(last_stage_output, aux_microbatch) -> scalar`` (e.g.
        targets packed in ``aux``); the per-microbatch losses are
        averaged.
      params: pytree with leading ``n_stages`` dim (see
        :func:`stack_stage_params`).
      xs: ``[n_micro, micro_batch, ...]`` inputs (replicated).
      aux: ``[n_micro, ...]`` per-microbatch loss side input (replicated).
      mesh: mesh containing ``axis``.

    Returns ``(loss, grads)`` with ``loss`` the mean over microbatches and
    ``grads`` matching ``params`` (each stage's slice is that stage's
    gradient), both replicated/sharded exactly like the inputs.
    """
    n_stages = int(mesh.shape[axis])
    n_micro = int(xs.shape[0])
    for leaf in jax.tree.leaves(params):
        if np.ndim(leaf) == 0 or np.shape(leaf)[0] != n_stages:
            raise ValueError(
                f"params leaf has leading dim "
                f"{np.shape(leaf)[0] if np.ndim(leaf) else 'none (scalar)'} "
                f"!= mesh axis {axis}={n_stages}; stack exactly one param "
                f"set per stage")
    param_spec = jax.tree.map(
        lambda leaf: P(axis, *(None,) * (np.ndim(leaf) - 1)), params)
    slots = 2 * n_stages
    # last tick = stage 0's backward of the final microbatch:
    # t = (2S - 1 - 0) + (n_micro - 1) = n_micro + 2S - 2, inclusive
    n_ticks = n_micro + 2 * n_stages - 1

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(param_spec, P(), P()),
             out_specs=(P(), param_spec),
             check_vma=False)
    def _one_f_one_b(p_shard, xs_rep, aux_rep):
        stage = jax.lax.axis_index(axis)
        last = stage == n_stages - 1
        p_local = jax.tree.map(lambda leaf: leaf[0], p_shard)
        perm_fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        perm_bwd = [(i, (i - 1) % n_stages) for i in range(n_stages)]

        micro_shape = xs_rep.shape[1:]
        state0 = jnp.zeros(micro_shape, xs_rep.dtype)
        stash0 = jnp.zeros((slots,) + micro_shape, xs_rep.dtype)
        dp0 = jax.tree.map(lambda leaf: jnp.zeros(leaf.shape[1:], jnp.float32),
                           p_shard)

        def tick(carry, t):
            fwd_in, cot_in, stash, dp, loss_acc = carry

            # ---- forward slot: microbatch m_f = t - stage --------------
            m_f = t - stage
            valid_f = jnp.logical_and(m_f >= 0, m_f < n_micro)
            feed = jax.lax.dynamic_index_in_dim(
                xs_rep, jnp.clip(m_f, 0, n_micro - 1), keepdims=False)
            x_in = jnp.where(stage == 0, feed, fwd_in)
            y = stage_fn(p_local, x_in)
            # stash the INPUT (remat recomputes the rest in the bwd slot)
            slot_f = jax.lax.rem(jnp.clip(m_f, 0, n_micro - 1) + slots,
                                 slots)
            stashed = jax.lax.dynamic_update_index_in_dim(
                stash, x_in.astype(stash.dtype), slot_f, axis=0)
            stash = jnp.where(valid_f, stashed, stash)

            # ---- backward slot: microbatch m_b = t - (2S - 1 - stage) --
            m_b = t - (2 * n_stages - 1 - stage)
            valid_b = jnp.logical_and(m_b >= 0, m_b < n_micro)
            slot_b = jax.lax.rem(jnp.clip(m_b, 0, n_micro - 1) + slots,
                                 slots)
            x_saved = jax.lax.dynamic_index_in_dim(stash, slot_b,
                                                   keepdims=False)
            aux_b = jax.lax.dynamic_index_in_dim(
                aux_rep, jnp.clip(m_b, 0, n_micro - 1), keepdims=False)
            y_b, vjp = jax.vjp(stage_fn, p_local, x_saved)
            # seed: the last stage differentiates the loss of ITS output;
            # earlier stages consume the cotangent ppermuted from above
            loss_b, dloss_dy = jax.value_and_grad(loss_fn)(y_b, aux_b)
            seed = jnp.where(last, dloss_dy.astype(y_b.dtype),
                             cot_in.astype(y_b.dtype))
            dp_m, dx_m = vjp(seed)
            dp = jax.tree.map(
                lambda acc, g: acc + jnp.where(valid_b,
                                               g.astype(jnp.float32), 0.0),
                dp, dp_m)
            loss_acc = loss_acc + jnp.where(
                jnp.logical_and(last, valid_b), loss_b, 0.0)

            # ---- ring moves (activation dtype pinned to the input's) ---
            fwd_out = jax.lax.ppermute(y.astype(xs_rep.dtype), axis,
                                       perm_fwd)
            cot_out = jax.lax.ppermute(dx_m.astype(xs_rep.dtype), axis,
                                       perm_bwd)
            return (fwd_out, cot_out, stash, dp, loss_acc), None

        carry0 = (state0, jnp.zeros(micro_shape, xs_rep.dtype), stash0, dp0,
                  jnp.float32(0.0))
        (_, _, _, dp, loss_acc), _ = jax.lax.scan(
            tick, carry0, jnp.arange(n_ticks))
        # loss lives on the last stage; masked psum replicates it
        loss = jax.lax.psum(
            jnp.where(last, loss_acc, 0.0), axis) / n_micro
        # grads: re-attach each stage's leading dim for the P(stage) spec
        dp = jax.tree.map(lambda g: g[None] / n_micro, dp)
        return loss, dp

    return _one_f_one_b(params, xs, aux)
