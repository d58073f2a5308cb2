"""``ops.paged_attention.paged_mq_attention`` on the CPU (interpreted):
the kernel against the view path it replaces, in both call forms.

* two pools, ``q`` spread block-diagonally over ``d_model``
  (``models.transformer``: ``_cached_attention(_paged_view(...))``);
* one pool, the absorbed latent query, values the first ``rkv`` columns
  of the key rows (``models.longcat``: ``mla_latent(_view(...))``);

on random bfloat16 pools with ragged lengths and shuffled block tables,
at toy widths and at the serving cells' (768 lanes x 12 heads; 640 lanes
x 64 heads, 512 of them values), within bfloat16 tolerance (the kernel's
softmax is online: un-normalised probabilities are rounded to bfloat16
where the view path rounds normalised ones). Then the edges: a dead
lane, one position, a block boundary on either side, a full slot, pad
table entries on scratch block 0, and NaN planted in every dead row and
dead block, none of which may reach an output. Last, ``decode_step_paged``
of both models with the kernel forced against the view path: the same
greedy tokens over 32 steps.

What only the chip shows (tiling, VMEM, the copies' speed) is
``tests/test_tpu_compile.py``'s and ``tools/paged_attention_probe.py``'s.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from multiverso_tpu.models import longcat, transformer
from multiverso_tpu.ops.paged_attention import (kernel_applies,
                                                paged_mq_attention,
                                                step_attention)

BS = 16
KERNEL = partial(paged_mq_attention, interpret=True)
# bfloat16 probabilities and values: 2^-9 a term, outputs of order 1
TOL = 2e-2


def _pools(rng, n_pools, groups, slots, per_slot, width):
    """``n_pools`` random bfloat16 pools ``[G, N + 1, Bs, W]`` and a
    block table ``[S, M]`` that deals the N blocks out shuffled."""
    N = slots * per_slot + 1
    pools = [jnp.asarray(rng.standard_normal((groups, N, BS, width)),
                         jnp.bfloat16) for _ in range(n_pools)]
    tables = (1 + rng.permutation(slots * per_slot)).reshape(
        slots, per_slot).astype(np.int32)
    return pools, tables


def _pad_tables(tables, lengths):
    """Entries past a slot's live blocks point at scratch block 0, as
    the engine leaves them."""
    live = np.arange(tables.shape[1])[None, :] < -(-lengths // BS)[:, None]
    return np.where(live, tables, 0).astype(np.int32)


def _plant_nan(pool, tables, lengths):
    """NaN in every row of the pool no live position maps to: dead rows
    of a slot's last block, every dead block, scratch block 0, in every
    group."""
    live = np.zeros(pool.shape[1:3], bool)
    for s, n in enumerate(lengths):
        for j in range(-(-int(n) // BS)):
            live[tables[s, j], : min(BS, int(n) - j * BS)] = True
    rows = np.array(pool.astype(jnp.float32))
    rows[:, ~live] = np.nan
    return jnp.asarray(rows, jnp.bfloat16)


def _gpt2_pair(rng, slots, per_slot, d_model, n_heads, lengths, layer=1,
               nan=False, tile_blocks=4):
    """(kernel, view) outputs [S, D] of one layer's attention."""
    (k_pool, v_pool), tables = _pools(rng, 2, 2, slots, per_slot, d_model)
    tables = _pad_tables(tables, lengths)
    q = jnp.asarray(rng.standard_normal((slots, d_model)) * 0.3,
                    jnp.bfloat16)
    pos = jnp.asarray(np.maximum(lengths - 1, 0))
    want = transformer._cached_attention(
        q, transformer._paged_view(k_pool, layer, jnp.asarray(tables)),
        transformer._paged_view(v_pool, layer, jnp.asarray(tables)),
        n_heads, pos)
    if nan:
        k_pool = _plant_nan(k_pool, tables, lengths)
        v_pool = _plant_nan(v_pool, tables, lengths)
    L, N, _, D = k_pool.shape
    q_heads, own = transformer._spread_heads(q, n_heads)
    full = KERNEL(q_heads, k_pool.reshape(L * N, BS, D),
                  v_pool.reshape(L * N, BS, D), layer * N + tables,
                  jnp.asarray(lengths), scale=1 / np.sqrt(D // n_heads),
                  wv=D, tile_blocks=tile_blocks)
    got = transformer._own_columns(full, own).astype(q.dtype)
    return (np.asarray(got, np.float32), np.asarray(want, np.float32))


def _latent_cfg(heads, rkv, rope, nope, v_dim, hidden):
    return longcat.LongCatConfig(
        vocab_size=64, hidden_size=hidden, num_layers=1,
        num_attention_heads=heads, kv_lora_rank=rkv, q_lora_rank=32,
        qk_rope_head_dim=rope, qk_nope_head_dim=nope, v_head_dim=v_dim,
        n_routed_experts=2, total_routed_experts=2, zero_expert_num=2,
        moe_topk=2, max_position_embeddings=4096, dtype=jnp.bfloat16)


def _longcat_pair(rng, cfg, slots, per_slot, lengths, sub=1, nan=False,
                  tile_blocks=4):
    """(kernel, view) outputs [S, D] of one MLA sublayer, latent form."""
    H, rkv = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    W = cfg.pool_width
    (pool,), tables = _pools(rng, 1, 2, slots, per_slot, W)
    pool = pool.at[..., cfg.cache_width:].set(0)    # the row's pad lanes
    tables = _pad_tables(tables, lengths)
    bf = lambda *shape: jnp.asarray(
        rng.standard_normal(shape) * shape[0] ** -0.5, jnp.bfloat16)
    w = {"w_kvb": bf(rkv, H * (dn + dv)), "w_o": bf(H * dv, cfg.hidden_size)}
    q_nope = jnp.asarray(rng.standard_normal((slots, H, dn)), jnp.bfloat16)
    q_rope = jnp.asarray(rng.standard_normal((slots, H, dr)), jnp.bfloat16)
    pos = jnp.asarray(np.maximum(lengths - 1, 0))
    T = per_slot * BS
    want = longcat.mla_latent(
        cfg, w, q_nope, q_rope,
        longcat._view(pool, sub, jnp.asarray(tables), T), pos)
    if nan:
        pool = _plant_nan(pool, tables, lengths)
    n_sub, N = pool.shape[:2]
    o_lat = KERNEL(longcat.latent_query(cfg, w, q_nope, q_rope, W),
                   pool.reshape(n_sub * N, BS, W), None, sub * N + tables,
                   jnp.asarray(lengths), scale=(dn + dr) ** -0.5, wv=rkv,
                   tile_blocks=tile_blocks)
    got = longcat.latent_output(cfg, w, o_lat)
    return np.asarray(got, np.float32), np.asarray(want, np.float32)


def _ragged(rng, slots, per_slot):
    lengths = rng.integers(1, per_slot * BS + 1, slots).astype(np.int32)
    lengths[rng.integers(slots)] = 0                 # one dead lane
    return lengths


def _close(got, want, lengths):
    live = lengths > 0
    assert np.isfinite(got).all()
    assert np.abs(want[live]).max() > 0.05           # a live comparison
    np.testing.assert_allclose(got[live], want[live], atol=TOL, rtol=TOL)
    assert not got[~live].any()                      # dead lanes: zeros


# toy widths, then the serving cells' own (few slots, short tables)
@pytest.mark.parametrize("d_model,n_heads,slots,per_slot,tile_blocks", [
    (128, 4, 6, 5, 2), (768, 12, 5, 9, 4), (768, 12, 3, 64, 16)],
    ids=["toy", "cell-width", "cell-table"])
def test_two_pool_kernel_matches_view(d_model, n_heads, slots, per_slot,
                                      tile_blocks):
    rng = np.random.default_rng(d_model + per_slot)
    lengths = _ragged(rng, slots, per_slot)
    got, want = _gpt2_pair(rng, slots, per_slot, d_model, n_heads, lengths,
                           tile_blocks=tile_blocks)
    _close(got, want, lengths)


@pytest.mark.parametrize("heads,rkv,rope,nope,v_dim,slots,per_slot", [
    (4, 16, 8, 16, 16, 6, 5), (64, 512, 64, 128, 128, 4, 10)],
    ids=["toy", "cell-width"])
def test_one_pool_kernel_matches_view(heads, rkv, rope, nope, v_dim, slots,
                                      per_slot):
    rng = np.random.default_rng(heads)
    cfg = _latent_cfg(heads, rkv, rope, nope, v_dim, hidden=64)
    assert cfg.pool_width == (128 if rkv == 16 else 640)
    lengths = _ragged(rng, slots, per_slot)
    got, want = _longcat_pair(rng, cfg, slots, per_slot, lengths)
    _close(got, want, lengths)


# lengths of 4 slots x 6 blocks (96 positions): each case is an edge
_EDGES = {
    "dead-lane-first": [0, 40, 96, 7],
    "dead-lane-last": [33, 17, 5, 0],
    "all-dead-but-one": [0, 0, 50, 0],
    "all-dead": [0, 0, 0, 0],
    "length-1": [1, 1, 0, 1],
    "pos-at-block-start": [17, 33, 49, 65],          # pos % 16 == 0
    "pos-at-block-end": [16, 32, 64, 80],            # pos % 16 == 15
    "full-slot": [96, 96, 96, 96],
    "one-tile-exactly": [32, 64, 31, 33],            # tile_blocks = 2
}


@pytest.mark.parametrize("form", ["two-pool", "one-pool"])
@pytest.mark.parametrize("edge", list(_EDGES))
def test_edges_and_planted_nan(edge, form):
    """Every edge with NaN in all dead rows, dead blocks and scratch:
    the outputs are finite and those of the clean pools' view path."""
    lengths = np.asarray(_EDGES[edge], np.int32)
    rng = np.random.default_rng(len(edge))
    if form == "two-pool":
        got, want = _gpt2_pair(rng, 4, 6, 128, 4, lengths, nan=True,
                               tile_blocks=2)
    else:
        got, want = _longcat_pair(rng, _latent_cfg(4, 16, 8, 16, 16, 64), 4,
                                  6, lengths, nan=True, tile_blocks=2)
    assert np.isfinite(got).all()
    live = lengths > 0
    if live.any():
        np.testing.assert_allclose(got[live], want[live], atol=TOL, rtol=TOL)
    assert not got[~live].any()


def test_dead_table_entries_are_never_read():
    """Table entries past a slot's live blocks may hold anything, ids out
    of the pool's bounds too: the kernel copies live blocks only."""
    rng = np.random.default_rng(3)
    lengths = np.asarray([20, 0, 96, 47], np.int32)
    (k_pool, v_pool), tables = _pools(rng, 2, 1, 4, 6, 128)
    q = jnp.asarray(rng.standard_normal((4, 4, 128)), jnp.bfloat16)
    call = lambda t: np.asarray(KERNEL(
        q, k_pool[0], v_pool[0], jnp.asarray(t), jnp.asarray(lengths),
        scale=0.2, wv=128, tile_blocks=2))
    wild = np.where(
        np.arange(6)[None, :] < -(-lengths // BS)[:, None], tables, 10 ** 6)
    np.testing.assert_array_equal(call(wild), call(tables))


def test_kernel_applies_only_to_whole_tile_bfloat16_blocks_on_a_tpu(
        monkeypatch):
    from multiverso_tpu.ops import paged_attention as module

    assert not kernel_applies(jnp.bfloat16, 16, 768)        # the CPU
    assert step_attention(jnp.bfloat16, 16, 768) is None
    monkeypatch.setattr(module, "_on_tpu", lambda: True)
    assert kernel_applies(jnp.bfloat16, 16, 768)
    assert step_attention(jnp.bfloat16, 16, 768) is paged_mq_attention
    assert step_attention(jnp.float32, 16, 768) is None
    assert kernel_applies(jnp.bfloat16, 32, 640)
    for dtype, block, width in [(jnp.float32, 16, 768), (jnp.int8, 32, 768),
                                (jnp.bfloat16, 8, 768), (jnp.bfloat16, 4, 768),
                                (jnp.bfloat16, 16, 576), (jnp.bfloat16, 0, 768)]:
        assert not kernel_applies(dtype, block, width)


# -- the two decode steps, kernel forced against the view path ------------------
_STEPS, _SLOTS, _PER_SLOT = 32, 4, 4


def _greedy(step, pools, prompts_len):
    """``_STEPS`` greedy steps from positions ``prompts_len`` (slot 2
    dead throughout); returns the tokens [steps, S]."""
    tables = (1 + np.arange(_SLOTS * _PER_SLOT, dtype=np.int32)).reshape(
        _SLOTS, _PER_SLOT)
    tok = jnp.asarray([5, 9, 0, 3], jnp.int32)
    pos = jnp.asarray(prompts_len, jnp.int32)
    active = jnp.asarray([True, True, False, True])
    out = []
    for _ in range(_STEPS):
        *pools, tok, pos = step(*pools, jnp.asarray(tables), tok, pos,
                                active)
        out.append(np.asarray(tok))
    return np.stack(out)


def test_transformer_decode_step_kernel_matches_view_tokens():
    """In float32 (the interpreter takes any dtype): this toy's logits
    lie closer together than bfloat16's rounding, and the two paths
    round their probabilities at different points, so in bfloat16 one
    near-tie flips a token and every later one with it. What is held
    here is the call site: the layer's base in the tables, the lengths,
    the scale, each head's own columns."""
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=128, n_heads=4, n_layers=2, d_ff=256,
        max_seq=64, dtype=jnp.float32)
    params = transformer.init_params(cfg, np.random.default_rng(3))
    pool = jnp.zeros((cfg.n_layers, _SLOTS * _PER_SLOT + 1, BS, cfg.d_model),
                     jnp.float32)
    runs = []
    for attend in (None, KERNEL):
        step = jax.jit(lambda kc, vc, bt, tok, pos, act, attend=attend:
                       transformer.decode_step_paged(
                           cfg, params, kc, vc, bt, tok, pos, act,
                           t_logical=_PER_SLOT * BS, paged_attention=attend))
        runs.append(_greedy(step, (pool, pool), [0, 15, 7, 16]))
    view, kernel = runs
    assert len(np.unique(view[:, [0, 1, 3]])) > 8       # no stuck token
    np.testing.assert_array_equal(kernel, view)


def test_longcat_decode_step_kernel_matches_view_tokens():
    cfg = longcat.LongCatConfig(
        vocab_size=256, hidden_size=64, ffn_hidden_size=128,
        expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
        kv_lora_rank=16, q_lora_rank=32, qk_rope_head_dim=8, v_head_dim=16,
        qk_nope_head_dim=16, n_routed_experts=8, total_routed_experts=32,
        expert_offset=8, zero_expert_num=16, moe_topk=6,
        max_position_embeddings=512, dtype=jnp.bfloat16, seed=11)
    params = longcat.init_params(cfg)
    pool = jnp.zeros((cfg.n_sublayers, _SLOTS * _PER_SLOT + 1, BS,
                      cfg.pool_width), jnp.bfloat16)
    counters = jnp.zeros(
        (cfg.num_layers, longcat.COUNT_SCALARS + cfg.n_routed_experts),
        jnp.float32)
    runs = []
    for attend in (None, KERNEL):
        step = jax.jit(lambda pool, cnt, bt, tok, pos, act, attend=attend:
                       longcat.decode_step_paged(
                           cfg, params, pool, cnt, bt, tok, pos, act,
                           _PER_SLOT * BS, paged_attention=attend))
        runs.append(_greedy(step, (pool, counters), [0, 15, 7, 16]))
    view, kernel = runs
    assert len(np.unique(view[:, [0, 1, 3]])) > 8
    np.testing.assert_array_equal(kernel, view)
