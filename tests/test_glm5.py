"""The ``glm_moe_dsa`` share (``models/glm5.py``; the benchmark's
``glm5-ep16``) against its plain reference, at a toy size on the CPU,
seeded random weights, float32 (``benchmarks/reference/glm5-ep16.py``
imports nothing of the program): the forward pass and the chunk
program's logits with the top-k selection biting; chunked prefill then
decode THROUGH ``DecodeEngine`` (logits of the served tokens), a prefix
hit among them whose index keys come with the copied block; the decode
step's selected positions against the reference's, and the positions
the served programs' probe reports (the cell's check); the layer at
contexts within the top-k equal to dense MLA; the indexer's kernel
(interpreted) against its gathered form; the index keys' whole-row
write against a write of their lanes alone; the exact top-k mask against
``jax.lax.top_k``; the one-group router; the shares of an expert layer;
and what the model refuses.

The toy keeps the shapes the selection lives in: 4 index heads of 32
whose first 8 values turn, a top-16 under contexts of up to 93, 4 MLA
heads of 24 + 8 / 32, a leading dense layer before two expert layers,
2 of 32 routed experts held, top-4 in one group."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness import load_module
from multiverso_tpu.log import FatalError
from multiverso_tpu.models import deepseek_v3, from_config, glm5, longcat
from multiverso_tpu.ops import route_group_limited
from multiverso_tpu.ops import sparse_attention as dsa
from multiverso_tpu.serving.programs import EngineSpec

K = 16
TOY = dict(
    model="glm_moe_dsa", vocab_size=256, hidden_size=64,
    intermediate_size=160, moe_intermediate_size=32, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, kv_lora_rank=16,
    q_lora_rank=32, qk_rope_head_dim=8, qk_nope_head_dim=24, v_head_dim=32,
    qk_head_dim=32, n_routed_experts=2, expert_offset=0, n_shared_experts=1,
    n_group=1, topk_group=1, num_experts_per_tok=4, norm_topk_prob=True,
    routed_scaling_factor=2.5, max_position_embeddings=4096,
    rms_norm_eps=1e-5, rope_interleave=True, indexer_rope_interleave=True,
    rope_parameters=dict(rope_theta=1000000, rope_type="default"),
    index_n_heads=4, index_head_dim=32, index_topk=K, dtype="float32",
    published={"n_routed_experts": 32})
SEED = 7
# float32 on both sides at the highest matmul precision, products in
# other orders (the latent form, chunks, the gate mask): sums of a few
# hundred terms of size ~1 differ by ~1e-5. A selection is exact on both
# sides (ties to the lower position), and a position that an order of
# the sum moved across the 16th score would move a logit by ~0.1 and
# fail, so none may.
TOL = 2e-4


@pytest.fixture(scope="module")
def ref():
    return load_module("reference", "glm5-ep16")


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(seed, n=60):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


@pytest.mark.parametrize("seed,n", [(7, 12), (7, 60), (2147483001, 70),
                                    (7, 300)])
def test_forward_matches_reference_logits(ref, seed, n):
    """Within the top-k (12 positions), past it, and at 300 tokens (the
    grouped held experts, every query past it)."""
    lm = from_config(TOY, seed)
    toks = _tokens(seed, n)
    want = np.asarray(ref.logits(TOY, seed % (2 ** 31 - 1), [toks])[0])
    np.testing.assert_allclose(np.asarray(lm.logits(toks)), want, atol=TOL)
    assert want.std() > 0.5         # logits of a live model, not zeros


@pytest.mark.parametrize("fault", ["dense", "topk_half", "topk_one_wider",
                                   "no_relu", "index_unrotated",
                                   "no_key_norm", "weights_ignored",
                                   "neighbour_keys", "no_routed",
                                   "gates_unnormalised", "no_shared"])
def test_reference_faults_move_the_logits(ref, fault):
    """Each planted fault of the reference is far outside ``TOL``, every
    routed expert held (a share of 2 in 32 meets a wrong pick too rarely
    for the router's faults to show in 70 tokens)."""
    assert fault in ref.FAULTS
    uncut = dict(TOY, n_routed_experts=32)
    toks = _tokens(3, 70)
    want = np.asarray(ref.logits(uncut, SEED, [toks])[0])
    bad = np.asarray(ref.logits(uncut, SEED, [toks], fault=fault)[0])
    assert np.abs(bad - want).max() > 0.05


# -- the two programs, called as the engine calls them ------------------------
def _programs(lm, slots=3, budget=16, T=96, Bs=8):
    M = -(-T // Bs)
    spec = EngineSpec(
        name="t", slots=slots, max_prompt=T - 16, max_new=16, cache_len=T,
        block_size=Bs, blocks_per_seq=M, pool_blocks=slots * M, budget=budget,
        prefix=False, tp=1, mesh=None, kv_quant="none", param_quant="none",
        spec_k=0, prefill_sp="none", donate=False)
    progs = lm.serving_programs(spec)
    pools = [jnp.zeros(shape, dtype) for shape, dtype in progs.pools]
    tables = 1 + np.arange(slots * M, dtype=np.int32).reshape(slots, M)
    return progs, pools, tables


def _prefill(progs, params, pools, tables, slot, prompt, budget=16):
    logits = None
    for off in range(0, len(prompt), budget):
        n = min(budget, len(prompt) - off)
        toks = np.zeros(budget, np.int32)
        toks[:n] = prompt[off:off + n]
        *pools, logits = progs.chunk(params, *pools, tables, np.int32(slot),
                                     toks, np.int32(off), np.int32(n))
    return pools, logits


@pytest.mark.parametrize("n", [10, 16, 17, 45, 70])
def test_chunk_program_logits_are_the_references(ref, n):
    """A prompt within the top-k, of exactly one chunk, of two, three and
    five chunks (every query of the later chunks past the top-k, and
    their selections reaching into the earlier chunks' index keys): the
    last chunk's logits are the reference's at the prompt's last
    position."""
    lm = from_config(TOY, SEED)
    progs, pools, tables = _programs(lm)
    # the slot's blocks hold ANOTHER prompt's rows and keys first
    pools, _ = _prefill(progs, lm.params, pools, tables, 1, _tokens(99, 80))
    prompt = _tokens(n, n)
    pools, logits = _prefill(progs, lm.params, pools, tables, 1, prompt)
    want = np.asarray(ref.logits(TOY, SEED, [prompt])[0])[-1]
    np.testing.assert_allclose(np.asarray(logits), want, atol=TOL)


@pytest.mark.parametrize("offset,n", [(0, 16), (40, 16), (64, 16),
                                      (80, 9)])
def test_chunk_with_selection_is_the_dense_form(monkeypatch, offset, n):
    """A chunk with the selection on, its attention over 8-position key
    tiles (12 over the 96-position slot), against the same chunk whose
    attention is ``longcat.mla_expanded`` over every position under the
    same mask: at offsets 0, 40 (the selection biting), 64 and the last
    chunk, short, the logits and every row the chunk wrote in every
    layer (so every valid row of every layer's input) agree."""
    lm = from_config(TOY, SEED)
    monkeypatch.setattr(longcat, "KEY_TILE", 8)

    def dense(cfg, w, q_nope, q_rope, rows, mask, reach, gate=None):
        return longcat.mla_expanded(cfg, w, q_nope, q_rope, rows, mask, gate)

    def run():
        progs, pools, tables = _programs(lm)
        pools, _ = _prefill(progs, lm.params, pools, tables, 1,
                            _tokens(99, 89))
        toks = np.zeros(16, np.int32)
        toks[:n] = _tokens(offset, n)
        *pools, logits = progs.chunk(lm.params, *pools, tables,
                                     np.int32(1), toks, np.int32(offset),
                                     np.int32(n))
        return np.asarray(pools[0])[:, tables[1]], np.asarray(logits)

    pool, logits = run()
    monkeypatch.setattr(longcat, "mla_expanded_tiled", dense)
    want_pool, want_logits = run()
    np.testing.assert_allclose(logits, want_logits, atol=1e-5)
    np.testing.assert_allclose(pool, want_pool, atol=1e-5)


def test_step_program_tokens_are_the_references(ref):
    """Prefill, then 16 decode steps over three slots at once (one dead
    lane among them at first): every served token is within ``TOL`` of
    the best logit of the reference's full forward pass at its
    position."""
    lm = from_config(TOY, SEED)
    progs, pools, tables = _programs(lm)
    prompts = [_tokens(s, n) for s, n in ((1, 40), (2, 63), (3, 75))]
    tok = np.zeros(3, np.int32)
    for s, p in enumerate(prompts):
        pools, logits = _prefill(progs, lm.params, pools, tables, s, p)
        tok[s] = int(np.argmax(np.asarray(logits)))
    pos = np.asarray([len(p) for p in prompts], np.int32)
    served = [[int(t)] for t in tok]
    for i in range(15):
        active = np.asarray([i > 0, True, True])
        *pools, nxt, pos = progs.step(lm.params, *pools, tables, tok, pos,
                                      active)
        # a dead lane's token comes back 0: the slot keeps its own
        tok = np.where(active, np.asarray(nxt), tok)
        for s in range(3):
            if active[s]:
                served[s].append(int(tok[s]))
    seqs = [np.concatenate([p, np.asarray(o, np.int32)])
            for p, o in zip(prompts, served)]
    gaps = ref.token_gap_tables(TOY, SEED, seqs, [len(p) for p in prompts])
    for g in gaps:
        assert len(g) >= 15 and g.max() <= TOL, g


def test_within_the_top_k_the_layer_is_dense_mla():
    """At contexts up to ``index_topk`` every query selects all it sees:
    the model's logits are DeepSeek-V3's dense MLA forward pass over the
    same weights; past it they are not."""
    lm = from_config(TOY, SEED)
    toks = _tokens(5, 40)
    dense = np.asarray(deepseek_v3.forward(lm.config, lm.params,
                                           jnp.asarray(toks)))
    got = np.asarray(lm.logits(toks))
    np.testing.assert_allclose(got[:K], dense[:K], atol=TOL)
    assert np.abs(got[K:] - dense[K:]).max() > 0.05


# -- the selection ------------------------------------------------------------
def _layer0_index(ref, lm, toks):
    """Layer 0's index scores of ``toks`` [T, T], the program's
    (``indexer_project`` and ``index_scores`` over the keys as the pool
    holds them, written and read through ``write_index_keys`` /
    ``index_keys``) and the reference's."""
    cfg, T = lm.config, len(toks)
    pos = jnp.arange(T)
    w = lm.params["layers"][0]["mla"]
    x = longcat.rmsnorm(jnp.take(lm.params["embed"], toks, axis=0),
                        w["norm"], cfg.rms_norm_eps, cfg.dtype)
    q, k, wt = glm5.indexer_project(cfg, w, x, longcat.query_latent(cfg, w, x),
                                    pos)
    Bs = 8
    M = -(-T // Bs)
    pool = jnp.zeros((cfg.n_sublayers, M + 1, Bs, cfg.pool_width), cfg.dtype)
    table = jnp.arange(1, M + 1, dtype=jnp.int32)
    pool = glm5.write_index_keys(cfg, pool, 0, k, table[pos // Bs], pos % Bs)
    mine = dsa.index_scores(q, wt, glm5.index_keys(cfg, pool, 0, table, T))
    rw = ref.layer_weights(TOY, SEED, 0)
    rx = ref.rmsnorm(jnp.take(ref.outer_weights(TOY, SEED)["embed"], toks,
                              axis=0), rw["norm"], cfg.rms_norm_eps)
    mm = ref._ops("")
    rq, rk, rwt = ref.indexer(TOY, mm, rw, rx, ref.rmsnorm(
        mm("td,dr->tr", rx, rw["w_qa"]), rw["q_norm"], cfg.rms_norm_eps))
    theirs = jnp.sum(rwt[:, :, None] * jax.nn.relu(
        jnp.einsum("tjd,sd->tjs", rq, rk)), 1)
    return np.asarray(mine), np.asarray(theirs)


def test_step_selection_is_the_references(ref):
    """The positions a decode step at each position ``t`` attends
    (``jax.lax.top_k`` of the scores over ``0..t``) are the reference's,
    exactly, and the chunk's mask picks the same sets."""
    lm = from_config(TOY, SEED)
    T = 90
    mine, theirs = _layer0_index(ref, lm, _tokens(8, T))
    np.testing.assert_allclose(mine, theirs, rtol=1e-5, atol=1e-5)
    causal = np.tril(np.ones((T, T), bool))
    masked = lambda s: jnp.where(causal, s, -jnp.inf)
    mask = np.asarray(dsa.top_k_mask(masked(mine), K))
    for t in range(K, T):
        got = set(np.asarray(jax.lax.top_k(masked(mine)[t], K)[1]).tolist())
        want = set(np.asarray(
            jax.lax.top_k(masked(theirs)[t], K)[1]).tolist())
        assert got == want, t
        assert set(np.flatnonzero(mask[t]).tolist()) == got, t
    # within the top-k every visible position
    assert (mask[:K] == causal[:K]).all()


def test_attended_positions_are_the_references(ref):
    """What the cell's check holds the program to: the positions decode
    queries attend in every layer, as the served chunk and step choose
    them (``GLM5LM.attended_positions``), are the reference's own
    (``padded_logits(queries=)``), layer by layer, in float32; a query
    within the top-k reads every earlier position and marks the rest of
    its columns -1."""
    lm = from_config(TOY, SEED)
    toks = _tokens(9, 80)
    queries = np.array([5, 15, 16, 40, 63, 78], np.int32)
    got = lm.attended_positions(toks, queries, block_size=4, cache_len=96,
                                chunk=16)
    L = TOY["num_hidden_layers"]
    assert got.shape == (L, len(queries), K) and got.dtype == np.int32
    want = ref.padded_logits(TOY, SEED, [toks], queries=[queries])[1]
    want = want.selected[0]
    assert want.shape == (L, len(queries), len(toks))
    for l in range(L):
        for i, t in enumerate(queries):
            mine = set(got[l, i][got[l, i] >= 0].tolist())
            assert mine == set(np.flatnonzero(want[l, i]).tolist()), (l, t)
            assert len(mine) == min(K, t + 1) and max(mine) <= t
    assert (got[:, 0, 6:] == -1).all()


@pytest.mark.parametrize("Bs,lane", [(16, 0), (64, 128)])
def test_index_kernel_is_the_gathered_form(Bs, lane):
    """``paged_index_scores`` (interpreted) against ``index_scores`` over
    the slot's gathered keys: a dead lane, a slot ending inside a block,
    one ending on a tile's edge; 128-lane keys at a lane offset in a
    256-wide row, the layout the step's kernel reads."""
    rng = np.random.default_rng(Bs)
    S, Hi, dI, M = 4, 32, 128, 640 // Bs
    pool = jnp.asarray(rng.standard_normal((S * M + 1, Bs, 256)),
                       jnp.bfloat16)
    tables = jnp.asarray(1 + rng.permutation(S * M).reshape(S, M), jnp.int32)
    lengths = jnp.asarray([0, 37, 512, 640], jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, Hi, dI)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((S, Hi)), jnp.float32)
    got = np.asarray(dsa.paged_index_scores(q, w, pool, tables, lengths,
                                            lane=lane, interpret=True))
    assert got.shape[0] == S and got.shape[1] >= M * Bs
    for s in range(S):
        n = int(lengths[s])
        keys = pool[tables[s]].reshape(M * Bs, 256)[:, lane:lane + dI]
        want = np.asarray(dsa.index_scores(q[s:s + 1], w[s:s + 1], keys))[0]
        assert np.isneginf(got[s, n:]).all()
        np.testing.assert_allclose(got[s, :n], want[:n], rtol=1e-5,
                                   atol=1e-4)


@pytest.mark.parametrize("l", range(5))
def test_write_index_keys_sets_only_its_lanes(l):
    """The cell's layout (``glm5-ep16.json``: five layers' 128-wide keys
    side by side in one 640-wide row after the five latent rows): layers
    0..``l`` write their keys in turn to the same rows, pad rows among
    them sent to scratch row (0, 0) four times, jitted as the programs
    run it. Bitwise, each layer's lanes of the named rows hold its keys,
    every other lane and row is as it was, and everything outside the
    scratch row is what the lane-window write of NumPy leaves."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "glm5-ep16.json")) as fh:
        cfg = glm5.config_from_dict(json.load(fh), 1)
    dI = cfg.index_head_dim
    rng = np.random.default_rng(l)
    pool0 = jnp.asarray(rng.standard_normal((cfg.n_sublayers, 9, 4,
                                             cfg.pool_width)), jnp.bfloat16)
    live = rng.permutation(8 * 4)[:8]
    block = np.insert(1 + live // 4, [2, 5, 5, 8], 0).astype(np.int32)
    offset = np.insert(live % 4, [2, 5, 5, 8], 0).astype(np.int32)
    pool, want = pool0, np.asarray(pool0).copy()
    for j in range(l + 1):
        keys = jnp.asarray(rng.standard_normal((block.size, dI)),
                           jnp.bfloat16)
        pool = jax.jit(lambda p, k, b, o, j=j: glm5.write_index_keys(
            cfg, p, j, k, b, o))(pool, keys, block, offset)
        sub, lane = glm5.index_location(cfg, j)
        assert sub == cfg.num_hidden_layers and lane == j * dI
        for i in range(block.size):
            want[sub, block[i], offset[i], lane:lane + dI] = keys[i]
    bits = lambda a: np.asarray(a).view(np.uint16)
    got, before = bits(pool), bits(pool0)
    np.testing.assert_array_equal(got[:, 1:], bits(want)[:, 1:])
    np.testing.assert_array_equal(got[:, 0, 1:], before[:, 0, 1:])
    np.testing.assert_array_equal(got[:-1, 0, 0], before[:-1, 0, 0])
    np.testing.assert_array_equal(got[-1, 0, 0, (l + 1) * dI:],
                                  before[-1, 0, 0, (l + 1) * dI:])


def test_top_k_mask_is_lax_top_k():
    """The chunk's mask from the exact k-th-largest search is
    ``jax.lax.top_k``'s set: distinct scores, ties at the k-th score
    (which ``top_k`` breaks to the lower position), zeros of both signs,
    rows with k or fewer finite scores (all of them kept) and none."""
    rng = np.random.default_rng(0)
    rows = [rng.standard_normal(200), rng.integers(-3, 4, 200) * 0.5,
            np.concatenate([rng.standard_normal(10), np.full(190, -np.inf)]),
            np.full(200, -np.inf), np.where(rng.random(200) < 0.5, 0.0, -0.0)
            + (rng.random(200) < 0.1) * rng.standard_normal(200)]
    scores = jnp.asarray(np.stack(rows), jnp.float32)
    for k in (1, 16, 17, 199, 200):
        mask = np.asarray(dsa.top_k_mask(scores, k))
        for r in range(len(rows)):
            finite = np.isfinite(rows[r])
            top = np.asarray(jax.lax.top_k(scores[r], k)[1])
            want = np.zeros(200, bool)
            want[top] = True
            np.testing.assert_array_equal(mask[r], want & finite, (k, r))


# -- through the engine -------------------------------------------------------
@pytest.fixture(scope="module")
def mv_session_module():
    import multiverso_tpu as mv

    mv.init(["test", "-log_level=error"])
    yield mv
    mv.shutdown()


@pytest.fixture(scope="module")
def served(mv_session_module, ref):
    """Eight requests through InferenceServer -> DecodeEngine on a paged
    latent pool of three layers and one row of index keys: 16-token
    chunks, 4-token blocks; the 7th repeats the 4th's prompt (a full
    prefix hit: copy-on-write of the last block, its index keys with it),
    the 8th shares the 3rd's first 24 tokens."""
    from multiverso_tpu.serving import InferenceServer

    with jax.default_matmul_precision("highest"):
        lm = from_config(TOY, SEED)
        srv = InferenceServer("t")
        eng = srv.register_decoder("lm", lm, slots=4, max_prompt=72,
                                   max_new=12, kv_block_size=4,
                                   prefill_token_budget=16)
        eng.warmup()
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 256, n).astype(np.int32)
                   for n in (5, 16, 41, 72, 17, 60)]
        prompts.append(prompts[3].copy())
        prompts.append(np.concatenate(
            [prompts[2][:24], rng.integers(0, 256, 33).astype(np.int32)]))
        outs = []
        for group in (prompts[:6], prompts[6:]):
            futs = [srv.submit("lm", {"prompt": p, "max_new": 12})
                    for p in group]
            outs += [np.asarray(f.result(timeout=300)["result"])
                     for f in futs]
        seqs = [np.concatenate([p, o]).astype(np.int32)
                for p, o in zip(prompts, outs)]
        gaps = ref.token_gap_tables(TOY, SEED, seqs,
                                    [len(p) for p in prompts])
        stats = eng.stats()
        srv.stop()
    return {"outs": outs, "gaps": gaps, "stats": stats, "eng": eng,
            "lm": lm}


@pytest.mark.parametrize("case,rows", [
    ("cold", range(0, 6)), ("full_hit_copy_on_write", [6]),
    ("partial_prefix_hit", [7])])
def test_engine_matches_reference_logits(served, case, rows):
    """Chunked prefill then decode over the paged latent pool: every
    served token is within ``TOL`` of the best logit of the reference's
    one full forward pass at its position, and every answer is whole."""
    for i in rows:
        assert len(served["outs"][i]) == 12
        assert served["gaps"][i].max() <= TOL, (case, i, served["gaps"][i])


def test_engine_pool_holds_the_index_keys_under_the_block_table(served):
    s, eng, lm = served["stats"], served["eng"], served["lm"]
    cfg = lm.config
    assert s["prefix_hits"] > 0 and s["cow_copies"] == 1
    assert s["step_traces"] == 1 and s["prefill_traces"] == 1
    # pool 0: a latent row a layer ((16 + 8) values in a 128-lane row),
    # then ONE row that packs the three layers' 32-wide index keys
    assert cfg.index_rows == 1 and cfg.n_sublayers == 4
    assert eng._pools[0].shape == (4, 4 * 21 + 1, 4, 128)
    assert [glm5.index_location(cfg, l) for l in range(3)] \
        == [(3, 0), (3, 32), (3, 64)]
    # counters: a row an EXPERT layer, then the attention's own
    assert eng._pools[1].shape == (3, longcat.COUNT_SCALARS + 2 + 1)


def test_selection_counters_in_stats(served):
    """Over the steps: every live position scored, ``min(length, 16)``
    attended, a layer each; a step run a step."""
    s = served["stats"]
    assert s["dsa_step_runs"] > 0 and s["dsa_chunk_runs"] > 0
    assert s["dsa_chunk_tokens"] >= sum(len(o) for o in served["outs"])
    assert 0 < s["dsa_attended_positions"] < s["dsa_scored_positions"]
    assert s["dsa_selected_share"] == pytest.approx(
        s["dsa_attended_positions"] / s["dsa_scored_positions"])
    assert s["dsa_attended_positions"] <= 3 * K * 4 * s["dsa_step_runs"]
    assert abs(s["moe_held_pairs_per_token"] - 0.25) < 0.15


# -- the expert layer ---------------------------------------------------------
def _layer_inputs(seed=0, T=24):
    u = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (T, TOY["hidden_size"])), jnp.float32)
    return u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True))


def _program_layer(cfg_dict, u):
    """``(y, counts, the shared expert's part of y)``."""
    from multiverso_tpu.ops.moe import swiglu

    cfg = glm5.config_from_dict(cfg_dict, SEED)
    layer = glm5.init_params(cfg)["layers"][1]
    y, counts = deepseek_v3.expert_layer(cfg, layer, u)
    return (np.asarray(y), np.asarray(counts),
            np.asarray(swiglu(u, **layer["shared"])))


def test_shares_add_up_to_the_uncut_layer(ref):
    """The 16 shares' routed parts, plus the shared expert ONCE, are the
    reference's uncut expert layer (all 32 routed experts held)."""
    u = _layer_inputs()
    uncut = dict(TOY, n_routed_experts=32, expert_offset=0)
    want = np.asarray(ref.expert_layer(uncut, ref._ops(""),
                                       ref.layer_weights(uncut, SEED, 1), u))
    shared = _program_layer(TOY, u)[2]
    total = shared.copy()               # every share computes it alike
    pairs = 0.0
    for share in range(16):
        y, counts, same = _program_layer(
            dict(TOY, expert_offset=2 * share), u)
        np.testing.assert_array_equal(same, shared)
        total += y - shared
        pairs += counts[3]
    np.testing.assert_allclose(total, want, atol=TOL)
    assert pairs == 4 * u.shape[0]      # every pick is some share's
    np.testing.assert_allclose(_program_layer(uncut, u)[0], want, atol=TOL)
    assert np.abs(shared).max() > 0.1


def test_one_group_router_is_a_plain_top_k_of_score_plus_bias():
    """``route_group_limited`` with one group of 256, one kept: the 8
    largest ``sigmoid(u W_r) + bias``, gates the picks' scores normalised
    to 2.5, the one group kept for every token."""
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    rw = jnp.asarray(rng.standard_normal((32, 256)) / np.sqrt(32),
                     jnp.float32)
    bias = jnp.asarray(rng.standard_normal(256) * 0.02, jnp.float32)
    idx, gates, kept = route_group_limited(u, rw, bias, top_k=8, n_group=1,
                                           topk_group=1, scale=2.5)
    s = jax.nn.sigmoid(u @ rw)
    want = jax.lax.top_k(s + bias, 8)[1]
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want))
    g = jnp.take_along_axis(s, want, -1)
    np.testing.assert_allclose(np.asarray(gates),
                               np.asarray(2.5 * g / g.sum(-1, keepdims=True)),
                               rtol=1e-6)
    assert np.asarray(kept).all()


# -- what the model refuses, by name ------------------------------------------
@pytest.mark.parametrize("feature,kwargs", [
    ("spec_k", dict(spec_k=2)),
    ("kv_quant", dict(kv_quant="int8")),
    ("param_quant", dict(decode_param_quant="int8")),
])
def test_unsupported_features_refused_at_construction(mv_session, feature,
                                                      kwargs):
    from multiverso_tpu.serving import InferenceServer

    lm = from_config(TOY, SEED)
    srv = InferenceServer("t")
    base = dict(slots=2, max_prompt=8, max_new=4, kv_block_size=4,
                prefill_token_budget=4)
    base.update(kwargs)
    with pytest.raises(FatalError, match=feature):
        srv.register_decoder("lm", lm, **base)


@pytest.mark.parametrize("change,match", [
    (dict(rope_parameters=dict(rope_type="yarn", rope_theta=10000,
                               factor=4)), "rope_type"),
    (dict(rope_interleave=False), "rope_interleave"),
    (dict(indexer_rope_interleave=False), "indexer_rope_interleave"),
    (dict(qk_head_dim=48), "qk_head_dim"),
    (dict(index_head_dim=48), "index keys"),
    (dict(expert_offset=31), "held experts"),
    (dict(n_shared_experts=0), "n_shared_experts"),
])
def test_a_configuration_the_model_cannot_run_is_refused(mv_session, change,
                                                         match):
    with pytest.raises(FatalError, match=match):
        from_config(dict(TOY, **change), 1)


def test_from_config_builds_the_kind_and_takes_no_overrides(mv_session):
    from multiverso_tpu.models import GLM5LM

    lm = from_config(TOY, 1)
    assert isinstance(lm, GLM5LM) and lm.config.rope_theta == 1e6
    assert lm.config.softmax_divisor == pytest.approx(32 ** 0.5)
    assert dataclasses.is_dataclass(lm.config)
    with pytest.raises(TypeError, match="takes no overrides"):
        from_config(TOY, 1, attention="reference")
