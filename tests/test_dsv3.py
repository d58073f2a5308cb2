"""The DeepSeek-V3-architecture share (``models/deepseek_v3.py``; the
benchmark's ``dots-vlm1-ep16``) against its plain reference, at a toy
size on the CPU, seeded random weights, float32
(``benchmarks/reference/dots-vlm1-ep16.py`` imports nothing of the
program): the full forward pass over a leading dense layer and two
expert layers; chunked prefill and decode THROUGH ``DecodeEngine``
(logits, not tokens); the two MLA forms with the YaRN scale on; the
shares of an expert layer with the shared expert counted once; the
group-limited sigmoid router; and what the model refuses at engine
construction.

The toy keeps the router's shape: 8 groups (of 4 experts), 4 kept, the
held experts half of group 0 (2 of 32), a nonzero bias; and YaRN with an
original context of 64, so that its ramp is at work over the toy's four
rotary frequencies (1, 0.05125, 2.5e-4, 2.5e-5)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness import load_module
from multiverso_tpu.log import FatalError
from multiverso_tpu.models import deepseek_v3 as dsv3
from multiverso_tpu.models import from_config, longcat
from multiverso_tpu.ops import route_group_limited

TOY = dict(
    model="deepseek_v3", vocab_size=256, hidden_size=64,
    intermediate_size=160, moe_intermediate_size=32, num_hidden_layers=3,
    first_k_dense_replace=1, num_attention_heads=4, kv_lora_rank=16,
    q_lora_rank=32, qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
    n_routed_experts=2, expert_offset=0, n_shared_experts=1, n_group=8,
    topk_group=4, num_experts_per_tok=4, norm_topk_prob=True,
    routed_scaling_factor=2.5, max_position_embeddings=4096,
    rms_norm_eps=1e-6, rope_theta=10000,
    rope_scaling=dict(type="yarn", factor=40, beta_fast=32, beta_slow=1,
                      mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=64),
    dtype="float32", published={"n_routed_experts": 32})
# float32 on both sides at the highest matmul precision, products in
# other orders (the latent form, the gate mask, chunks): sums of a few
# hundred terms of size ~1 differ by ~1e-5; a pick that flips on such a
# difference would move a logit by ~1e-2 and fail, so none may. The
# router's product in bfloat16 moves logits 5 x past it and more (below).
TOL = 2e-4


@pytest.fixture(scope="module")
def ref():
    return load_module("reference", "dots-vlm1-ep16")


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _tokens(seed, n=40):
    return np.random.default_rng(seed).integers(0, 256, n).astype(np.int32)


# 300 tokens: over ``ops.moe.GROUPED_MIN_ROWS``, the grouped form
@pytest.mark.parametrize("seed,n", [(7, 40), (2147483001, 40), (7, 300)])
def test_forward_matches_reference_logits(ref, seed, n):
    lm = from_config(TOY, seed)
    toks = _tokens(seed, n)
    want = np.asarray(ref.logits(TOY, seed, [toks])[0])
    np.testing.assert_allclose(np.asarray(lm.logits(toks)), want, atol=TOL)
    assert want.std() > 0.5         # logits of a live model, not zeros


@pytest.mark.parametrize("fault", ["no_routed", "gates_unnormalised",
                                   "no_group_limit", "no_bias", "no_shared",
                                   "no_mscale", "no_rope"])
def test_reference_faults_move_the_logits(ref, fault):
    """Each planted fault of the reference is far outside ``TOL``: the
    comparison above can see every part of the layer (every routed
    expert held: a share of 2 in 32 meets a wrong pick too rarely for 40
    tokens to show the router's faults)."""
    assert fault in ref.FAULTS
    uncut = dict(TOY, n_routed_experts=32)
    toks = _tokens(3)
    want = np.asarray(ref.logits(uncut, 7, [toks])[0])
    bad = np.asarray(ref.logits(uncut, 7, [toks], fault=fault)[0])
    assert np.abs(bad - want).max() > 0.05


@pytest.mark.parametrize("change", [
    dict(first_k_dense_replace=0), dict(first_k_dense_replace=3),
    dict(rope_scaling=None)],
    ids=["leading_layer_given_experts", "every_layer_dense", "no_yarn"])
def test_reference_layer_pattern_and_yarn_move_the_logits(ref, change):
    toks = _tokens(3)
    want = np.asarray(ref.logits(TOY, 7, [toks])[0])
    bad = np.asarray(ref.logits(dict(TOY, **change), 7, [toks])[0])
    assert np.abs(bad - want).max() > 0.05


@pytest.mark.parametrize("seed", [5, 6])
def test_a_bfloat16_router_fails_the_tolerance(ref, seed):
    """Only the router's product in bfloat16, every routed expert held
    (in a share a flipped pick shows only where it meets a held expert):
    scores move in the third digit and, within 200 tokens, a pick flips,
    so the logits move far past ``TOL`` (0.12 and 0.11 on these seeds),
    which is how tight the comparisons here are."""
    uncut = dict(TOY, n_routed_experts=32)
    toks = _tokens(seed, 200)
    exact = np.asarray(ref.logits(uncut, 7, [toks])[0])
    low = np.asarray(ref.logits(uncut, 7, [toks],
                                router_compute="bfloat16")[0])
    assert np.abs(low - exact).max() > 5 * TOL


# -- through the engine ---------------------------------------------------------
@pytest.fixture(scope="module")
def mv_session_module():
    import multiverso_tpu as mv

    mv.init(["test", "-log_level=error"])
    yield mv
    mv.shutdown()


@pytest.fixture(scope="module")
def served(mv_session_module, ref):
    """Eight requests through InferenceServer -> DecodeEngine on a paged
    latent pool of three layers: 16-token chunks, 4-token blocks; the
    7th repeats the 4th's prompt (a full prefix hit: copy-on-write of
    the last block), the 8th shares the 3rd's first 24 tokens."""
    from multiverso_tpu.serving import InferenceServer

    with jax.default_matmul_precision("highest"):
        lm = from_config(TOY, 7)
        srv = InferenceServer("t")
        eng = srv.register_decoder("lm", lm, slots=4, max_prompt=40,
                                   max_new=12, kv_block_size=4,
                                   prefill_token_budget=16)
        eng.warmup()
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 256, n).astype(np.int32)
                   for n in (5, 16, 33, 40, 17, 24)]
        prompts.append(prompts[3].copy())
        prompts.append(np.concatenate(
            [prompts[2][:24], rng.integers(0, 256, 9).astype(np.int32)]))
        outs = []
        for group in (prompts[:6], prompts[6:]):
            futs = [srv.submit("lm", {"prompt": p, "max_new": 12})
                    for p in group]
            outs += [np.asarray(f.result(timeout=300)["result"])
                     for f in futs]
        seqs = [np.concatenate([p, o]).astype(np.int32)
                for p, o in zip(prompts, outs)]
        gaps = ref.token_gaps(TOY, 7, seqs, [len(p) for p in prompts])
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
        assert served["gaps"][i] <= TOL, (case, i, served["gaps"][i])


def test_engine_one_trace_one_pool_and_the_weights_pinned(served):
    s, eng, lm = served["stats"], served["eng"], served["lm"]
    assert s["prefix_hits"] > 0 and s["cow_copies"] == 1
    assert s["step_traces"] == 1 and s["prefill_traces"] == 1
    assert eng.pool_drift() is None and not eng.supports_transfer
    # one latent pool [layers, N + 1, Bs, pool_width]: the (16 + 8)-wide
    # row in whole 128-lane tiles, a row a LAYER (the dense one too)
    assert lm.config.cache_width == 24
    assert eng._pools[0].shape == (3, 4 * 13 + 1, 4, 128)
    # counters: a row an EXPERT layer
    assert eng._pools[1].shape == (2, longcat.COUNT_SCALARS + 2 + 1)
    assert eng._pinned["layers"][1]["experts"]["w_up"] \
        is lm.params["layers"][1]["experts"]["w_up"]
    assert "router" not in lm.params["layers"][0] \
        and "ffn" not in lm.params["layers"][1]


def test_routing_counters_in_stats(served):
    """Design at the toy size: 4 picks over 32 outputs, 2 held; the held
    experts' group is among a token's 4 of 8 half the time."""
    s = served["stats"]
    assert s["moe_layer_tokens"] > 0
    assert s["moe_ffn_picks_per_token"] == 4.0
    assert abs(s["moe_held_pairs_per_token"] - 0.25) < 0.15
    assert abs(s["moe_home_group_share"] - 0.5) < 0.2
    assert s["moe_held_pairs_per_token"] <= 2 * s["moe_home_group_share"]
    assert 1.0 <= s["moe_held_load_max_over_mean"] <= 2.0


# -- latent attention: the two forms, the YaRN scale on -------------------------------
def test_mla_latent_form_equals_expanded_form_under_yarn():
    cfg = dsv3.config_from_dict(TOY, 5)
    assert abs(cfg.softmax_divisor
               - 24 ** 0.5 / (0.1 * np.log(40) + 1) ** 2) < 1e-9
    w = dsv3.init_params(cfg)["layers"][1]["mla"]
    rng = np.random.default_rng(0)
    S, T = 3, 21
    x = jnp.asarray(rng.standard_normal((S, T, cfg.hidden_size)),
                    jnp.float32)
    pos = jnp.asarray([20, 7, 0])
    got, want = [], []
    for s in range(S):
        q_nope, q_rope, rows = longcat.mla_project(
            cfg, w, x[s], jnp.arange(T))
        p = int(pos[s])
        mask = (jnp.arange(T) <= p)[None, :]
        want.append(longcat.mla_expanded(
            cfg, w, q_nope[p:p + 1], q_rope[p:p + 1], rows, mask)[0])
        got.append((q_nope[p], q_rope[p], rows))
    out = longcat.mla_latent(
        cfg, w, jnp.stack([g[0] for g in got]),
        jnp.stack([g[1] for g in got]), jnp.stack([g[2] for g in got]), pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jnp.stack(want)),
                               atol=TOL)
    # and the scale is ON: without it the same inputs give another output
    plain = dataclasses.replace(cfg, rope_scaling=dict(
        TOY["rope_scaling"], mscale=0, mscale_all_dim=0))
    q_nope, q_rope, rows = longcat.mla_project(cfg, w, x[0], jnp.arange(T))
    mask = jnp.ones((1, T), bool)
    a = longcat.mla_expanded(cfg, w, q_nope[-1:], q_rope[-1:], rows, mask)
    b = longcat.mla_expanded(plain, w, q_nope[-1:], q_rope[-1:], rows, mask)
    assert np.abs(np.asarray(a - b)).max() > 1e-3


def test_yarn_frequencies_as_published(ref):
    """At the published sizes: low 10, high 23; the program's and the
    reference's frequencies agree; ``m^2`` = 1.8739."""
    pub = dict(TOY, qk_rope_head_dim=64, qk_nope_head_dim=128,
               rope_scaling=dict(TOY["rope_scaling"],
                                 original_max_position_embeddings=4096))
    cfg = dsv3.config_from_dict(pub, 0)
    got = np.asarray(longcat.rope_frequencies(cfg))
    want, m = ref.yarn(pub)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    e = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:11], e[:11], rtol=2e-6)
    np.testing.assert_allclose(got[23:], e[23:] / 40, rtol=2e-6)
    assert e[11] / 40 < got[11] < e[11]
    assert abs(m * m - 1.8739) < 1e-4
    assert abs(192 ** 0.5 / cfg.softmax_divisor - m * m) < 1e-9


# -- the expert layer -----------------------------------------------------------
def _layer_inputs(seed=0, T=24):
    u = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (T, TOY["hidden_size"])), jnp.float32)
    return u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True))


def _ref_layer(ref, cfg, u, bias=None, shared=True):
    w = ref.layer_weights(cfg, 7, 1)
    if bias is not None:
        w["router_bias"] = jnp.asarray(bias, jnp.float32)
    return np.asarray(ref.expert_layer(cfg, ref._ops(""), w, u,
                                       shared=shared))


def _program_layer(cfg_dict, u, bias=None):
    """``(y, counts, the shared expert's part of y)``."""
    from multiverso_tpu.ops.moe import swiglu

    cfg = dsv3.config_from_dict(cfg_dict, 7)
    layer = dsv3.init_params(cfg)["layers"][1]
    if bias is not None:
        layer["router_bias"] = jnp.asarray(bias, jnp.float32)
    y, counts = dsv3.expert_layer(cfg, layer, u)
    return (np.asarray(y), np.asarray(counts),
            np.asarray(swiglu(u, **layer["shared"])))


def test_shares_add_up_to_the_uncut_layer(ref):
    """The 16 shares' routed parts, plus the shared expert ONCE, are the
    reference's uncut expert layer (all 32 routed experts held)."""
    u = _layer_inputs()
    uncut = dict(TOY, n_routed_experts=32, expert_offset=0)
    want = _ref_layer(ref, uncut, u)
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
    # the program's own uncut layer, and a share alone against the
    # reference's same share
    np.testing.assert_allclose(_program_layer(uncut, u)[0], want, atol=TOL)
    np.testing.assert_allclose(_program_layer(TOY, u)[0],
                               _ref_layer(ref, TOY, u), atol=TOL)
    # the shared expert is a large part of what a share gives
    assert np.abs(shared).max() > 0.1


def _route(u, rw, bias, **kw):
    args = dict(top_k=4, n_group=8, topk_group=4, scale=2.5)
    args.update(kw)
    return [np.asarray(a) for a in route_group_limited(u, rw, bias, **args)]


@pytest.fixture(scope="module")
def router_case():
    u = _layer_inputs(3, T=64)
    rw = jnp.asarray(np.random.default_rng(3).standard_normal((64, 32)),
                     jnp.float32) * 0.125
    bias = jnp.asarray(np.random.default_rng(4).standard_normal(32),
                       jnp.float32) * 0.05
    return u, rw, bias


def test_router_bias_changes_the_picks_and_no_gate(router_case):
    u, rw, bias = router_case
    s = np.asarray(jax.nn.sigmoid(u @ rw))
    idx0, g0, _ = _route(u, rw, jnp.zeros(32))
    idx1, g1, _ = _route(u, rw, bias)
    moved = (np.sort(idx0, -1) != np.sort(idx1, -1)).any(-1)
    assert 0 < moved.sum() < len(moved)
    for idx, g in ((idx0, g0), (idx1, g1)):
        picked = np.take_along_axis(s, idx, -1)
        np.testing.assert_allclose(
            g, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    # where the bias moved no pick, it moved no gate
    order = lambda i, g: np.take_along_axis(g, np.argsort(i, -1), -1)
    np.testing.assert_allclose(order(idx0, g0)[~moved],
                               order(idx1, g1)[~moved], rtol=1e-6)


def test_router_gates_sum_to_the_scaling_factor(router_case):
    u, rw, bias = router_case
    idx, gates, _ = _route(u, rw, bias)
    np.testing.assert_allclose(gates.sum(-1), 2.5, rtol=1e-5)
    # left unnormalised the 4 picked scores x 2.5 would sum to more
    picked = np.take_along_axis(np.asarray(jax.nn.sigmoid(u @ rw)), idx, -1)
    assert (2.5 * picked.sum(-1) > 3.0).all()


def test_router_never_more_than_four_groups_a_token(router_case):
    u, rw, bias = router_case
    idx, _, kept = _route(u, rw, bias)
    assert (kept.sum(-1) == 4).all()
    assert (np.take_along_axis(kept, idx // 4, -1)).all()
    assert max(len(set(row // 4)) for row in idx) <= 4
    # the limit binds: without it some token picks from a fifth group
    free, _, _ = _route(u, rw, bias, topk_group=8)
    assert (np.sort(free, -1) != np.sort(idx, -1)).any()
    # a group's score is the sum of its two best, not its best: plant
    # one huge score in group 7 and two large ones in group 6
    b = np.zeros(32, np.float32)
    b[28], b[24], b[25] = 0.9, 0.6, 0.6
    _, _, k = _route(u * 0, rw, jnp.asarray(b), topk_group=1)
    assert k[:, 6].all() and not k[:, 7].any()


def test_no_token_dropped_under_planted_imbalance(ref):
    """Every token on ONE held expert (and three absent ones of its
    group): its load is the whole token count, the home group is every
    token's, and the result is the reference's."""
    u = _layer_inputs(2, T=40)
    bias = np.zeros(32, np.float32)
    bias[[1, 2, 3, 0]] = 10.0           # 0 and 1 are held, 2 and 3 absent
    got, counts, _ = _program_layer(TOY, u, bias)
    np.testing.assert_allclose(got, _ref_layer(ref, TOY, u, bias), atol=TOL)
    assert counts[0] == 40 and counts[3] == 80
    S = longcat.COUNT_SCALARS
    assert (counts[S:S + 2] == 40).all() and counts[S + 2] == 40
    assert np.abs(got).max() > 0.1


# -- what the model lacks is refused, by name -------------------------------------
@pytest.mark.parametrize("feature,kwargs", [
    ("kv_quant", dict(kv_quant="int8")),
    ("param_quant", dict(decode_param_quant="int8")),
    ("spec_k", dict(spec_k=2)),
    ("decode_tp", dict(decode_tp=2)),
    ("prefill_sp", dict(prefill_sp=True)),
])
def test_unsupported_features_refused_at_construction(mv_session, feature,
                                                      kwargs):
    from multiverso_tpu.serving import InferenceServer

    if feature == "decode_tp" and len(jax.devices()) < 2:
        pytest.skip("needs two devices to reach the model's own refusal")
    lm = from_config(TOY, 7)
    srv = InferenceServer("t")
    base = dict(slots=2, max_prompt=8, max_new=4, kv_block_size=4,
                prefill_token_budget=4)
    base.update(kwargs)
    with pytest.raises(FatalError, match=feature):
        srv.register_decoder("lm", lm, **base)


@pytest.mark.parametrize("change,match", [
    (dict(expert_offset=31), "held experts"),
    (dict(n_group=5), "groups"),
    (dict(num_experts_per_tok=17), "picks"),
    (dict(first_k_dense_replace=4), "first_k_dense_replace"),
    (dict(rope_scaling=dict(TOY["rope_scaling"], mscale=0.5)), "mscale"),
    (dict(rope_scaling=dict(TOY["rope_scaling"], type="linear")),
     "rope_scaling"),
    (dict(rope_scaling=dict(TOY["rope_scaling"], factor=1)), "factor"),
    (dict(norm_topk_prob=False), "norm_topk_prob"),
    (dict(n_shared_experts=0), "n_shared_experts"),
])
def test_a_configuration_the_model_cannot_run_is_refused(mv_session, change,
                                                         match):
    with pytest.raises(FatalError, match=match):
        from_config(dict(TOY, **change), 1)


def test_from_config_builds_all_three_kinds(mv_session):
    from multiverso_tpu.models import DeepSeekV3LM, LongCatLM, TransformerLM
    from test_longcat import TOY as LONGCAT_TOY

    assert isinstance(from_config(TOY, 1), DeepSeekV3LM)
    assert isinstance(from_config(LONGCAT_TOY, 1), LongCatLM)
    lm = from_config(dict(model="transformer_lm", vocab_size=64, n_embd=32,
                          n_layer=1, n_head=2, n_inner=64, n_positions=16,
                          dtype="float32", learning_rate=0.1, momentum=0.9),
                     3, attention="reference")
    assert isinstance(lm, TransformerLM) and lm.config.seed == 3
    with pytest.raises(TypeError, match="takes no overrides"):
        from_config(TOY, 1, attention="reference")
