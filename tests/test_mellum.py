"""The ``mellum`` model (``models/mellum.py``; the benchmark's
``mellum2-12b-l8``) against its plain reference, at a toy size on the
CPU, seeded random weights, float32
(``benchmarks/reference/mellum2-12b-l8.py`` imports nothing of the
program): the full forward pass over three sliding-window layers and one
full YaRN layer; chunked prefill across chunk and window boundaries and
decode through both caches, THROUGH ``DecodeEngine`` and through the
engine's own two programs (logits, not tokens); the life cycle of the
per-slot ring (written after the chunk attends, left bit-identical where
a slot is not active, untouched by warm-up, recomputed after a
preemption); the step's kernel over both caches against its gathered
form; the router's normalised setting; LongCat's programs as before; and
what the model refuses at engine construction.

The toy keeps the shapes the mechanisms live in: 8 query heads over 2 KV
heads, a window of 16 positions under 16-token chunks and 8-token
blocks (prompts of up to 40 and answers of 12 run past it), 8 experts
top-2, and YaRN with ``original_max_position_embeddings`` 32, so that
it bites at these lengths."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness import ROOT, load_json, load_module
from multiverso_tpu.log import FatalError
from multiverso_tpu.models import from_config, mellum
from multiverso_tpu.serving.programs import EngineSpec

TOY = dict(
    model="mellum", vocab_size=160, hidden_size=48, num_hidden_layers=4,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=24,
    norm_topk_prob=True, attention_bias=False,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"] * 4, sliding_window=16,
    max_position_embeddings=4096, rms_norm_eps=1e-6,
    rope_parameters={
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000,
                           "factor": 4,
                           "original_max_position_embeddings": 32,
                           "beta_fast": 32, "beta_slow": 1},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    dtype="float32")
# float32 on both sides at the highest matmul precision, sums in other
# orders (blocks of queries, a ring's rows in another order, the gate
# mask, the kernel's online softmax): ~1e-5; a flipped pick or a key
# more or less in a window moves a logit by ~1e-2 and more
TOL = 2e-4
SEED = 7
W = TOY["sliding_window"]


@pytest.fixture(scope="module")
def ref():
    return load_module("reference", "mellum2-12b-l8")


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(
        0, TOY["vocab_size"], n).astype(np.int32)


# 300 tokens: over ``ops.moe.GROUPED_MIN_ROWS``, the grouped form
@pytest.mark.parametrize("seed,n", [(7, 40), (2147483001, 150), (7, 300)])
def test_forward_matches_reference_logits(ref, seed, n):
    lm = from_config(TOY, seed)
    toks = _tokens(seed, n)
    want = np.asarray(ref.logits(TOY, seed, [toks])[0])
    np.testing.assert_allclose(np.asarray(lm.logits(toks)), want, atol=TOL)
    assert want.std() > 0.5         # logits of a live model, not zeros


def test_reference_faults_each_move_the_logits(ref):
    """Each planted fault of the reference is far outside ``TOL`` at the
    toy's window, the off-by-one window among them (a 17th key of 16)."""
    toks = _tokens(3, 60)
    want = np.asarray(ref.logits(TOY, SEED, [toks])[0])
    assert len(ref.FAULTS) == 8
    for fault in ref.FAULTS:
        bad = np.asarray(ref.logits(TOY, SEED, [toks], fault=fault)[0])
        assert np.abs(bad - want).max() > 0.1, fault


def test_layer_kinds_rotary_and_window():
    cfg = mellum.config_from_dict(TOY, SEED)
    assert [cfg.is_full(l) for l in range(4)] == [False] * 3 + [True]
    assert (cfg.n_sublayers, cfg.n_sliding, cfg.pool_width) == (1, 3, 64)
    inv_s, scale_s = mellum.rotary(cfg, False)
    inv_f, scale_f = mellum.rotary(cfg, True)
    assert scale_s == 1.0 and abs(scale_f - (0.1 * np.log(4) + 1)) < 1e-9
    # YaRN leaves the fastest pairs and slows the slowest by the factor
    assert np.allclose(inv_f[0], inv_s[0])
    assert np.allclose(inv_f[-1], inv_s[-1] / 4)
    # the cell's file: three sliding layers to one full, twice
    full = mellum.config_from_dict(load_json(
        ROOT, "benchmarks", "configs", "mellum2-12b-l8.json"), 1)
    assert [full.is_full(l) for l in range(8)] == [False, False, False,
                                                  True] * 2
    assert (full.pool_width, full.ring_block) == (1024, 512)


# -- through the engine ---------------------------------------------------------
@pytest.fixture(scope="module")
def mv_session_module():
    import multiverso_tpu as mv

    mv.init(["test", "-log_level=error"])
    yield mv
    mv.shutdown()


def _engine(srv, lm, **kw):
    base = dict(slots=4, max_prompt=40, max_new=12, kv_block_size=8,
                prefill_token_budget=16, prefix_cache=False)
    base.update(kw)
    eng = srv.register_decoder("lm", lm, **base)
    eng.warmup()
    return eng


def _serve(srv, prompts, max_new=12):
    futs = [srv.submit("lm", {"prompt": p, "max_new": max_new})
            for p in prompts]
    return [np.asarray(f.result(timeout=300)["result"]) for f in futs]


def _gaps(ref, prompts, outs):
    seqs = [np.concatenate([p, o]).astype(np.int32)
            for p, o in zip(prompts, outs)]
    return [float(t.max()) for t in ref.token_gap_tables(
        TOY, SEED, seqs, [len(p) for p in prompts])]


@pytest.fixture(scope="module")
def served(mv_session_module, ref):
    """Eight requests through InferenceServer -> DecodeEngine, 16-token
    chunks and 8-token blocks on 4 slots: prompts of one chunk and of
    two and three (a ring row is read by a later chunk, and prompts of
    more than a window), answers that run past the window, twice as many
    requests as slots (every slot and ring is reused)."""
    from multiverso_tpu.serving import InferenceServer

    with jax.default_matmul_precision("highest"):
        lm = from_config(TOY, SEED)
        srv = InferenceServer("t")
        eng = _engine(srv, lm)
        after_warm = np.asarray(eng._pools[1])
        prompts = [_tokens(10 + i, n)
                   for i, n in enumerate((5, 16, 33, 40, 17, 24, 3, 32))]
        outs = _serve(srv, prompts)
        gaps = _gaps(ref, prompts, outs)
        stats = eng.stats()
        srv.stop()
    return {"outs": outs, "gaps": gaps, "stats": stats, "eng": eng,
            "after_warm": after_warm}


@pytest.mark.parametrize("case,rows", [
    ("inside_the_window", [0, 6]), ("past_the_window", [1, 2, 3, 4, 5, 7])])
def test_engine_matches_reference_logits(served, case, rows):
    """Chunked prefill then decode over the block pool and the rings:
    every served token is within ``TOL`` of the best logit of the
    reference's one full forward pass at its position, and every answer
    is whole."""
    for i in rows:
        assert len(served["outs"][i]) == 12
        assert served["gaps"][i] <= TOL, (case, i, served["gaps"][i])


def test_engine_pools_traces_and_counters(served):
    s, eng = served["stats"], served["eng"]
    assert s["step_traces"] == 1 and s["prefill_traces"] == 1
    assert eng.pool_drift() is None
    # pool 0 block-shaped (one full layer), the rings, the counters: a
    # row a layer and one of the attention's own
    assert eng._pools[0].shape == (1, 4 * 7 + 1, 8, 64)
    assert eng._pools[1].shape == (3, 4, W, 64)
    assert eng._pools[2].shape == (5, mellum.COUNT_SCALARS + 8)
    assert s["slot_state_bytes_per_device"] == 4 * 3 * W * 64 * 4
    # warm-up ran both programs on the live pools and left the rings zero
    assert not served["after_warm"].any()
    # 11 steps a request (the last chunk gives the first token), each
    # reading min(pos + 1, 16) rows of a ring and pos + 1 of the pool
    prompts = (5, 16, 33, 40, 17, 24, 3, 32)
    assert s["swa_step_tokens"] == 8 * 11
    assert s["swa_full_positions"] == sum(
        p + n + 1 for p in prompts for n in range(11))
    assert s["swa_sliding_positions"] == sum(
        min(p + n + 1, W) for p in prompts for n in range(11))
    assert 0.4 < s["swa_read_share"] < 0.6
    # 2 picks over 8 experts, all held
    assert s["moe_held_pairs_per_token"] == 2.0


# -- the engine's own two programs, called as the engine calls them -------------
def _programs(lm, slots=3, budget=16, T=64, Bs=8, ring_attention=None):
    M = -(-T // Bs)
    spec = EngineSpec(
        name="t", slots=slots, max_prompt=T - 8, max_new=8, cache_len=T,
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


@pytest.mark.parametrize("n", [7, 16, 17, 45])
def test_chunk_program_logits_are_the_references(ref, n):
    """A prompt of one, exactly one, two and three chunks (past the
    window): the last chunk's logits are the reference's at the prompt's
    last position."""
    lm = from_config(TOY, SEED)
    progs, pools, tables = _programs(lm)
    # the slot's ring holds ANOTHER prompt's rows first
    pools, _ = _prefill(progs, lm.params, pools, tables, 1, _tokens(99, 30))
    prompt = _tokens(n, n)
    pools, logits = _prefill(progs, lm.params, pools, tables, 1, prompt)
    want = np.asarray(ref.logits(TOY, SEED, [prompt])[0])[-1]
    np.testing.assert_allclose(np.asarray(logits), want, atol=TOL)


def test_a_chunk_that_writes_its_ring_rows_first_is_wrong(ref, monkeypatch):
    """The order the docstring insists on: a chunk that wrote its rows
    into the ring BEFORE attending would overwrite the keys of positions
    a window back that its earlier queries still read."""
    real = mellum.attend_rows

    lm = from_config(TOY, SEED)
    prompt = _tokens(5, 40)
    want = np.asarray(ref.logits(TOY, SEED, [prompt])[0])[-1]
    progs, pools, tables = _programs(lm)
    _, good = _prefill(progs, lm.params, pools, tables, 0, prompt)
    np.testing.assert_allclose(np.asarray(good), want, atol=TOL)

    def early(cfg, w, q, rows, mask):
        if rows.shape[0] != W + 16:     # a full layer's view
            return real(cfg, w, q, rows, mask)
        # the chunk's rows written over the ring part first
        C = q.shape[0]
        ring, new = rows[:W], rows[W:]
        ring = ring.at[jnp.arange(C) % W].set(new)
        return real(cfg, w, q, jnp.concatenate([ring, new]), mask)

    monkeypatch.setattr(mellum, "attend_rows", early)
    progs, pools, tables = _programs(lm)
    _, bad = _prefill(progs, lm.params, pools, tables, 0, prompt)
    assert np.abs(np.asarray(bad) - want).max() > 0.05


def test_step_program_leaves_an_inactive_slot_bit_identical(ref):
    """Three slots prefilled past the window; a step with slot 1 not
    active: its ring comes back bit for bit, the live slots' move, and a
    live slot's next token is the reference's greedy choice."""
    lm = from_config(TOY, SEED)
    progs, pools, tables = _programs(lm)
    prompts = [_tokens(40 + s, 9 + 9 * s) for s in range(3)]
    first = []
    for s, p in enumerate(prompts):
        pools, logits = _prefill(progs, lm.params, pools, tables, s, p)
        first.append(int(np.argmax(np.asarray(logits))))
    before = [np.asarray(p) for p in pools]
    active = np.array([True, False, True])
    *after, nxt, pos = progs.step(
        lm.params, *pools, tables, np.asarray(first, np.int32),
        np.asarray([len(p) for p in prompts], np.int32), active)
    ring = np.asarray(after[1])
    assert np.array_equal(ring[:, 1], before[1][:, 1])
    for s in (0, 2):
        assert not np.array_equal(ring[:, s], before[1][:, s])
    assert list(np.asarray(pos)) == [len(prompts[0]) + 1, len(prompts[1]),
                                     len(prompts[2]) + 1]
    for s in (0, 2):
        seq = np.concatenate([prompts[s], [first[s]]]).astype(np.int32)
        want = np.asarray(ref.logits(TOY, SEED, [seq])[0])[-1]
        assert want.max() - want[int(nxt[s])] <= TOL
    assert int(nxt[1]) == 0


def test_step_kernel_over_both_caches_is_the_gathered_form():
    """The step with ``paged_mq_attention`` (interpreted) over the full
    layer's blocks and, under its own name, the rings' blocks gives the
    gathered form's pools, tokens and counters: the grouped query's
    layout, the ring's table and lengths are the kernel's contract."""
    import functools

    from multiverso_tpu.ops import paged_attention

    lm = from_config(TOY, SEED)
    progs, pools, tables = _programs(lm)
    prompts = [_tokens(60 + s, 6 + 11 * s) for s in range(3)]
    for s, p in enumerate(prompts):
        pools, _ = _prefill(progs, lm.params, pools, tables, s, p)
    args = (np.asarray([3, 5, 7], np.int32),
            np.asarray([len(p) for p in prompts], np.int32),
            np.array([True, True, False]))
    cfg = lm.config
    T = 64
    view = mellum.decode_step_paged(cfg, lm.params, *pools, tables, *args,
                                    T)
    kernel = mellum.decode_step_paged(
        cfg, lm.params, *pools, tables, *args, T,
        paged_attention=paged_attention.paged_mq_attention,
        ring_attention=functools.partial(paged_attention.paged_mq_attention,
                                         name=mellum.SWA_KERNEL))
    # (block 0 is scratch: a dead lane parks its row there, a different
    # number in each form)
    for a, b in zip((view[0][:, 1:],) + view[1:3],
                    (kernel[0][:, 1:],) + kernel[1:3]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert np.array_equal(np.asarray(view[3]), np.asarray(kernel[3]))


def test_warmup_chunk_on_scratch_tables_writes_no_ring_row():
    lm = from_config(TOY, SEED)
    progs, pools, tables = _programs(lm)
    pools, _ = _prefill(progs, lm.params, pools, tables, 0, _tokens(5, 20))
    before = np.asarray(pools[1])
    *after, _ = progs.chunk(lm.params, *pools, np.zeros_like(tables),
                            np.int32(0), np.ones(16, np.int32), np.int32(0),
                            np.int32(1))
    assert np.array_equal(np.asarray(after[1]), before)


def test_a_preempted_request_recomputes_to_the_same_tokens(
        mv_session_module, ref):
    """A pool too small for four sequences to grow in: growth preempts,
    the victims re-prefill from ``off == 0`` (prompt and emitted tokens),
    which rewrites their ring, and every answer is the reference's greedy
    one."""
    from multiverso_tpu.serving import InferenceServer

    lm = from_config(TOY, SEED)
    srv = InferenceServer("t")
    eng = _engine(srv, lm, slots=4, max_prompt=24, max_new=16,
                  kv_block_size=4, kv_pool_blocks=18,
                  prefill_token_budget=8)
    prompts = [_tokens(80 + i, n)
               for i, n in enumerate((19, 24, 5, 21, 13, 3))]
    outs = _serve(srv, prompts, max_new=16)
    stats = eng.stats()
    srv.stop()
    assert stats["preemptions"] > 0, "pool never pressured; geometry bug"
    assert all(len(o) == 16 for o in outs)
    assert max(_gaps(ref, prompts, outs)) <= TOL


# -- the router's normalised setting ------------------------------------------
def test_route_topk_normalised_is_a_softmax_over_the_picks():
    from multiverso_tpu.ops.moe import route_topk

    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((24, 48)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((48, 8)) / 7, jnp.float32)
    idx, gates = route_topk(u, w, jnp.zeros(8), 3, 2.0, normalize=True)
    logits = np.asarray(u, np.float64) @ np.asarray(w, np.float64)
    want_idx = np.argsort(-logits, -1)[:, :3]
    picked = np.take_along_axis(logits, want_idx, -1)
    want = 2.0 * np.exp(picked) / np.exp(picked).sum(-1, keepdims=True)
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.sort(want_idx, -1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), np.argsort(np.asarray(idx)), -1),
        np.take_along_axis(want, np.argsort(want_idx), -1), rtol=1e-5)
    # the default is as before: p itself, times the scale
    _, raw = route_topk(u, w, jnp.zeros(8), 3, 2.0)
    p = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    np.testing.assert_allclose(
        np.asarray(raw), 2.0 * np.take_along_axis(p, np.asarray(idx), -1),
        rtol=1e-5)


def _route_topk_before(u, router_w, router_bias, top_k, scale):
    """``ops.moe.route_topk`` as it was before it had ``normalize``."""
    logits = jnp.dot(u.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(p + router_bias.astype(jnp.float32), top_k)
    gates = scale * jnp.take_along_axis(p, idx, axis=-1)
    return idx.astype(jnp.int32), gates


@pytest.mark.parametrize("program", ["step", "chunk"])
def test_longcat_programs_lower_as_before(monkeypatch, program):
    """LongCat's step and chunk, which call ``route_topk`` unnormalised,
    lower to the same StableHLO text with the router as it is and as it
    was."""
    from multiverso_tpu.models import longcat

    cfg = longcat.LongCatConfig(
        vocab_size=64, hidden_size=32, ffn_hidden_size=48,
        expert_ffn_hidden_size=16, num_layers=1, num_attention_heads=2,
        kv_lora_rank=16, q_lora_rank=24, qk_rope_head_dim=8,
        qk_nope_head_dim=8, v_head_dim=8, n_routed_experts=4,
        total_routed_experts=8, zero_expert_num=2, moe_topk=3,
        max_position_embeddings=64, dtype=jnp.float32)
    lm = object.__new__(longcat.LongCatLM)
    lm.config = cfg
    params = jax.eval_shape(lambda: longcat.init_params(cfg))

    def text():
        progs, pools, tables = _programs(lm, slots=2, budget=8, T=32, Bs=4)
        S, i32 = 2, jnp.int32
        if program == "step":
            args = (params, *pools, tables, jnp.zeros(S, i32),
                    jnp.zeros(S, i32), jnp.ones(S, bool))
            return progs.step.lower(*args).as_text()
        args = (params, *pools, tables, i32(0), jnp.zeros(8, i32), i32(0),
                i32(8))
        return progs.chunk.lower(*args).as_text()

    now = text()
    monkeypatch.setattr(longcat, "route_topk", _route_topk_before)
    assert text() == now


# -- what the model lacks is refused, by name -------------------------------------
@pytest.mark.parametrize("feature,kwargs", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("spec_k", dict(spec_k=2)),
    ("kv_quant", dict(kv_quant="int8")),
    ("param_quant", dict(decode_param_quant="int8")),
    ("decode_tp", dict(decode_tp=2)),
    ("prefill_sp", dict(prefill_sp=True)),
])
def test_unsupported_features_refused_at_construction(mv_session, feature,
                                                      kwargs):
    from multiverso_tpu.serving import InferenceServer

    if feature == "decode_tp" and len(jax.devices()) < 2:
        pytest.skip("needs two devices to reach the model's own refusal")
    lm = from_config(TOY, SEED)
    srv = InferenceServer("t")
    base = dict(slots=2, max_prompt=8, max_new=4, kv_block_size=4,
                prefill_token_budget=4, prefix_cache=False)
    base.update(kwargs)
    with pytest.raises(FatalError, match=feature):
        srv.register_decoder("lm", lm, **base)


def test_programs_the_model_lacks_are_none(mv_session):
    lm = from_config(TOY, SEED)
    progs, _, _ = _programs(lm)
    assert progs.cow is progs.fetch is progs.splice is progs.verify is None
    assert progs.chunk_sp is None and progs.counter_pool == 2
    assert progs.bytes_per_slot == 3 * W * 64 * 4


@pytest.mark.parametrize("change,match", [
    (dict(mlp_layer_types=["sparse", "dense", "sparse", "sparse"]),
     "mlp_layer_types"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(layer_types=["sliding_attention"] * 4), "attention kinds"),
    (dict(layer_types=["sliding_attention"] * 3 + ["chunked_attention"]),
     "layer_types"),
    (dict(num_key_value_heads=3), "KV heads"),
    (dict(rope_parameters=dict(TOY["rope_parameters"], sliding_attention={
        "rope_type": "longrope", "rope_theta": 10000})), "rope_type"),
])
def test_a_configuration_the_model_cannot_run_is_refused(mv_session, change,
                                                         match):
    with pytest.raises(FatalError, match=match):
        from_config(dict(TOY, **change), 1)


def test_from_config_builds_the_kind_and_takes_no_overrides(mv_session):
    from multiverso_tpu.models import MellumLM

    assert isinstance(from_config(TOY, 1), MellumLM)
    with pytest.raises(TypeError, match="takes no overrides"):
        from_config(TOY, 1, attention="reference")
