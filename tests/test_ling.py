"""The ``bailing_hybrid`` share (``models/ling.py``; the benchmark's
``ling3-flash-ep4``) against its plain reference, at a toy size on the
CPU, seeded random weights, float32
(``benchmarks/reference/ling3-flash-ep4.py`` imports nothing of the
program): the full forward pass over five KDA layers, one MLA layer and
a sixth KDA layer, the first dense and six with experts; chunked prefill
and decode THROUGH ``DecodeEngine`` and through the engine's own two
programs (logits, not tokens); the life cycle of the per-slot pools
(reset at a prompt's first chunk, carried over chunks and steps, left
bit-identical where a slot is not active, untouched by warm-up, reused
by a later request, recomputed after a preemption); the shares of an
expert layer with the shared expert counted once; and what the model
refuses at engine construction.

The toy keeps the shapes: ``layer_group_size`` 6 over 7 layers, 4 taps,
the gate's bound of -5, a router of 4 groups of 8 experts with 2 kept,
8 of the 32 experts held (a quarter, as the cell)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness import load_module
from multiverso_tpu.log import FatalError
from multiverso_tpu.models import from_config, ling
from multiverso_tpu.serving.programs import EngineSpec

TOY = dict(
    model="bailing_hybrid", vocab_size=192, hidden_size=48,
    intermediate_size=96, moe_intermediate_size=24, num_hidden_layers=7,
    first_k_dense_replace=1, layer_group_size=6, num_attention_heads=3,
    head_dim=16, short_conv_kernel_size=4, kda_lower_bound=-5,
    kv_lora_rank=16, q_lora_rank=None, qk_rope_head_dim=8,
    qk_nope_head_dim=16, v_head_dim=16, num_experts=8, expert_offset=0,
    num_shared_experts=1, n_group=4, topk_group=2, num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=2.5,
    max_position_embeddings=4096, rms_norm_eps=1e-6, rope_theta=6000000,
    dtype="float32", published={"num_experts": 32})
# float32 on both sides at the highest matmul precision, sums in other
# orders (the chunkwise form against the token recurrence, the latent
# form, the gate mask): ~1e-5; a flipped pick would move a logit ~1e-2
TOL = 2e-4
SEED = 7


@pytest.fixture(scope="module")
def ref():
    return load_module("reference", "ling3-flash-ep4")


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(seed, n):
    return np.random.default_rng(seed).integers(
        0, TOY["vocab_size"], n).astype(np.int32)


@pytest.mark.parametrize("seed,n", [(7, 40), (2147483001, 150)])
def test_forward_matches_reference_logits(ref, seed, n):
    lm = from_config(TOY, seed)
    toks = _tokens(seed, n)
    want = np.asarray(ref.logits(TOY, seed, [toks])[0])
    np.testing.assert_allclose(np.asarray(lm.logits(toks)), want, atol=TOL)
    assert want.std() > 0.5         # logits of a live model, not zeros


def test_reference_faults_each_move_the_logits(ref, monkeypatch):
    """Each planted fault of the reference is far outside ``TOL`` (the
    two that drop what crosses a cut are told where the prompt ends and
    cut it every 16 positions). Unnormalised keys make the delta rule
    unstable (``1 - b |k|^2 < -1``): that fault's logits overflow, which
    the comparison reads as a failed number.)"""
    monkeypatch.setattr(ref, "FAULT_CHUNK", 16)
    toks = _tokens(3, 80)
    want = np.asarray(ref.logits(TOY, SEED, [toks])[0])
    assert len(ref.FAULTS) == 12
    for fault in ref.FAULTS:
        bad = np.asarray(ref.logits(TOY, SEED, [toks], fault=fault,
                                    prompt_lens=[50])[0])
        assert not np.abs(bad - want).max() <= 0.05, fault


def test_layer_pattern_five_kda_to_one_mla(ref):
    cfg = ling.config_from_dict(TOY, SEED)
    assert [cfg.is_mla(i) for i in range(7)] == [False] * 5 + [True, False]
    assert (cfg.n_kda_layers, cfg.n_sublayers, cfg.n_expert_layers) \
        == (6, 1, 6)
    params = jax.eval_shape(lambda: ling.init_params(cfg))
    kinds = ["w_kva" in layer["attn"] for layer in params["layers"]]
    assert kinds == [False] * 5 + [True, False]
    assert "ffn" in params["layers"][0] and "router" in params["layers"][1]
    assert "w_q" in params["layers"][5]["attn"] \
        and "w_qa" not in params["layers"][5]["attn"]       # no query latent
    assert params["layers"][5]["attn"]["w_og"].shape == (48, 3)
    # the reference with another period is another model
    toks = _tokens(1, 30)
    a = np.asarray(ref.logits(TOY, SEED, [toks])[0])
    b = np.asarray(ref.logits(dict(TOY, layer_group_size=3), SEED,
                              [toks])[0])
    assert np.abs(a - b).max() > 0.05


# -- through the engine ---------------------------------------------------------
@pytest.fixture(scope="module")
def mv_session_module():
    import multiverso_tpu as mv

    mv.init(["test", "-log_level=error"])
    yield mv
    mv.shutdown()


def _engine(srv, lm, **kw):
    base = dict(slots=4, max_prompt=40, max_new=12, kv_block_size=4,
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
    chunks and 4-token blocks on 4 slots: prompts of one chunk and of
    two and three (the state and the conv tail cross a chunk boundary),
    twice as many requests as slots (every slot is reused)."""
    from multiverso_tpu.serving import InferenceServer

    with jax.default_matmul_precision("highest"):
        lm = from_config(TOY, SEED)
        srv = InferenceServer("t")
        eng = _engine(srv, lm)
        after_warm = [np.asarray(p) for p in eng._pools[1:3]]
        prompts = [_tokens(10 + i, n)
                   for i, n in enumerate((5, 16, 33, 40, 17, 24, 3, 32))]
        outs = _serve(srv, prompts)
        gaps = _gaps(ref, prompts, outs)
        stats = eng.stats()
        srv.stop()
    return {"outs": outs, "gaps": gaps, "stats": stats, "eng": eng,
            "lm": lm, "after_warm": after_warm}


@pytest.mark.parametrize("case,rows", [
    ("one_chunk", [0, 1, 6]), ("several_chunks", [2, 3, 4, 5, 7])])
def test_engine_matches_reference_logits(served, case, rows):
    """Chunked prefill then decode over the latent pool and the per-slot
    pools: every served token is within ``TOL`` of the best logit of
    the reference's one full forward pass at its position, and every
    answer is whole."""
    for i in rows:
        assert len(served["outs"][i]) == 12
        assert served["gaps"][i] <= TOL, (case, i, served["gaps"][i])


def test_engine_pools_traces_and_counters(served):
    s, eng, lm = served["stats"], served["eng"], served["lm"]
    assert s["step_traces"] == 1 and s["prefill_traces"] == 1
    assert eng.pool_drift() is None
    # pool 0 block-shaped (one latent layer), then the two slot pools,
    # then the counters: a row an expert layer and one of the KDA layers'
    assert eng._pools[0].shape == (1, 4 * 13 + 1, 4, 128)
    assert eng._pools[1].shape == (6, 4, 3, 16, 16) \
        and eng._pools[1].dtype == jnp.float32
    assert eng._pools[2].shape == (6, 4, 3, 3 * 3 * 16)
    assert eng._pools[3].shape == (7, 4 + 8 + 1)
    assert s["slot_state_bytes_per_device"] == 4 * 6 * (
        3 * 16 * 16 * 4 + 3 * 144 * 4)
    # warm-up ran both programs on the live pools and left them zero
    for pool in served["after_warm"]:
        assert not pool.any()
    # one reset a request; the law's design value of the decay
    assert s["kda_state_resets"] == 8
    assert -0.2 < s["kda_mean_log_decay"] < -0.01
    # every prompt token and every decoded one (the last chunk gives an
    # answer's first token), and warm-up's one-token chunk
    assert s["kda_layer_tokens"] == 6 * (
        sum((5, 16, 33, 40, 17, 24, 3, 32)) + 8 * 11 + 1)
    # 4 picks over 32 outputs, 8 held: groups 0 of 4, 2 kept
    assert s["moe_ffn_picks_per_token"] == 4.0
    assert abs(s["moe_held_pairs_per_token"] - 1.0) < 0.4
    assert abs(s["moe_home_group_share"] - 0.5) < 0.2


# -- the engine's own two programs, called as the engine calls them -------------
def _programs(lm, slots=3, budget=16, T=64, Bs=4):
    M = -(-T // Bs)
    progs = lm.serving_programs(EngineSpec(
        name="t", slots=slots, max_prompt=T - 8, max_new=8, cache_len=T,
        block_size=Bs, blocks_per_seq=M, pool_blocks=slots * M, budget=budget,
        prefix=False, tp=1, mesh=None, kv_quant="none", param_quant="none",
        spec_k=0, prefill_sp="none", donate=False))
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
    """A prompt of one, exactly one, two and three chunks: the last
    chunk's logits are the reference's at the prompt's last position."""
    lm = from_config(TOY, SEED)
    progs, pools, tables = _programs(lm)
    # the slot holds ANOTHER prompt's state, tail and rows first
    pools, _ = _prefill(progs, lm.params, pools, tables, 1, _tokens(99, 30))
    prompt = _tokens(n, n)
    pools, logits = _prefill(progs, lm.params, pools, tables, 1, prompt)
    want = np.asarray(ref.logits(TOY, SEED, [prompt])[0])[-1]
    np.testing.assert_allclose(np.asarray(logits), want, atol=TOL)


def test_step_program_leaves_an_inactive_slot_bit_identical(ref):
    """Three slots prefilled; a step with slot 1 not active (the slot
    being prefilled behind the step): its state and tail come back bit
    for bit, the live slots' move, and a live slot's next token is the
    reference's greedy choice."""
    lm = from_config(TOY, SEED)
    progs, pools, tables = _programs(lm)
    prompts = [_tokens(40 + s, 9 + 4 * s) for s in range(3)]
    first = []
    for s, p in enumerate(prompts):
        pools, logits = _prefill(progs, lm.params, pools, tables, s, p)
        first.append(int(np.argmax(np.asarray(logits))))
    before = [np.asarray(p) for p in pools]
    active = np.array([True, False, True])
    *after, nxt, pos = progs.step(
        lm.params, *pools, tables, np.asarray(first, np.int32),
        np.asarray([len(p) for p in prompts], np.int32), active)
    for pool in (1, 2):
        assert np.array_equal(np.asarray(after[pool])[:, 1], before[pool][:, 1])
        for s in (0, 2):
            assert not np.array_equal(np.asarray(after[pool])[:, s],
                                      before[pool][:, s])
    assert list(np.asarray(pos)) == [len(prompts[0]) + 1, len(prompts[1]),
                                     len(prompts[2]) + 1]
    for s in (0, 2):
        seq = np.concatenate([prompts[s], [first[s]]]).astype(np.int32)
        want = np.asarray(ref.logits(TOY, SEED, [seq])[0])[-1]
        assert want.max() - want[int(nxt[s])] <= TOL
    assert int(nxt[1]) == 0


def test_warmup_chunk_on_scratch_tables_writes_no_slot_row():
    lm = from_config(TOY, SEED)
    progs, pools, tables = _programs(lm)
    pools, _ = _prefill(progs, lm.params, pools, tables, 0, _tokens(5, 20))
    before = [np.asarray(p) for p in pools]
    *after, _ = progs.chunk(lm.params, *pools, np.zeros_like(tables),
                            np.int32(0), np.ones(16, np.int32), np.int32(0),
                            np.int32(1))
    for pool in (1, 2):
        assert np.array_equal(np.asarray(after[pool]), before[pool])
    # and it counted no reset
    assert float(after[3][-1, 2]) == float(before[3][-1, 2]) == 1.0


def test_a_reused_slot_gives_a_fresh_engines_tokens(mv_session_module, ref):
    """One slot: the second request starts in the slot the first one
    left its state, tail and rows in, and is served as by an engine that
    never saw the first."""
    from multiverso_tpu.serving import InferenceServer

    lm = from_config(TOY, SEED)
    a, b = _tokens(70, 37), _tokens(71, 21)
    srv = InferenceServer("t")
    _engine(srv, lm, slots=1)
    both = _serve(srv, [a, b])
    srv.stop()
    srv = InferenceServer("t")
    _engine(srv, lm, slots=1)
    alone = _serve(srv, [b])
    srv.stop()
    np.testing.assert_array_equal(both[1], alone[0])
    assert max(_gaps(ref, [a, b], both)) <= TOL


def test_a_preempted_request_recomputes_to_the_same_tokens(
        mv_session_module, ref):
    """A pool too small for four sequences to grow in: growth preempts,
    the victims re-prefill from ``off == 0`` (prompt and emitted tokens),
    which resets their slot's state, and every answer is the reference's
    greedy one."""
    from multiverso_tpu.serving import InferenceServer

    lm = from_config(TOY, SEED)
    srv = InferenceServer("t")
    eng = _engine(srv, lm, slots=4, max_prompt=12, max_new=16,
                  kv_pool_blocks=10, prefill_token_budget=4)
    prompts = [_tokens(80 + i, n) for i, n in enumerate((9, 12, 5, 11, 7, 3))]
    outs = _serve(srv, prompts, max_new=16)
    stats = eng.stats()
    srv.stop()
    assert stats["preemptions"] > 0, "pool never pressured; geometry bug"
    assert all(len(o) == 16 for o in outs)
    assert max(_gaps(ref, prompts, outs)) <= TOL
    assert stats["kda_state_resets"] == len(prompts) + stats["preemptions"]


# -- the expert layer's shares --------------------------------------------------
def test_shares_add_up_to_the_uncut_layer(ref):
    """The four shares' routed parts, plus the shared expert ONCE, are
    the reference's uncut expert layer (all 32 routed experts held)."""
    from multiverso_tpu.models import deepseek_v3
    from multiverso_tpu.ops.moe import swiglu

    u = jnp.asarray(np.random.default_rng(0).standard_normal((24, 48)),
                    jnp.float32)
    u = u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True))
    uncut = dict(TOY, num_experts=32, expert_offset=0)
    want = np.asarray(ref.expert_layer(
        uncut, ref._ops(""), ref.layer_weights(uncut, SEED, 1), u))
    total, pairs = None, 0.0
    for share in range(4):
        cfg = ling.config_from_dict(dict(TOY, expert_offset=8 * share), SEED)
        layer = ling.init_params(cfg)["layers"][1]
        y, counts = deepseek_v3.expert_layer(cfg, layer, u)
        shared = np.asarray(swiglu(u, **layer["shared"]))
        total = shared.copy() if total is None else total
        total += np.asarray(y) - shared
        pairs += float(counts[3])
    np.testing.assert_allclose(total, want, atol=TOL)
    assert pairs == 4 * u.shape[0]      # every pick is some share's
    assert np.abs(shared).max() > 0.1


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
    assert progs.chunk_sp is None and progs.counter_pool == 3
    assert progs.bytes_per_slot == 6 * (3 * 16 * 16 * 4 + 3 * 144 * 4)


@pytest.mark.parametrize("change,match", [
    (dict(expert_offset=25), "held experts"),
    (dict(n_group=5), "groups"),
    (dict(first_k_dense_replace=8), "first_k_dense_replace"),
    (dict(num_hidden_layers=4), "attention kinds"),
    (dict(num_shared_experts=0), "num_shared_experts"),
    (dict(expert_swiglu_limit_list=[0, 0, 4, 0, 0, 0, 0]), "SwiGLU clamp"),
])
def test_a_configuration_the_model_cannot_run_is_refused(mv_session, change,
                                                         match):
    with pytest.raises(FatalError, match=match):
        from_config(dict(TOY, **change), 1)


def test_from_config_builds_the_kind_and_takes_no_overrides(mv_session):
    from multiverso_tpu.models import LingLM

    assert isinstance(from_config(TOY, 1), LingLM)
    with pytest.raises(TypeError, match="takes no overrides"):
        from_config(TOY, 1, attention="reference")
