"""LongCat-Flash share against its plain reference, at a toy size on the
CPU, seeded random weights, float32 (``benchmarks/reference/
longcat-flash-ep32.py`` imports nothing of the program): the full
forward pass; chunked prefill and decode THROUGH ``DecodeEngine`` (logits,
not tokens: the gap of each served token in the reference's logits); the
two MLA forms; the expert layer's shares, picks and planted imbalance;
and what the model refuses at engine construction."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.harness import load_module
from multiverso_tpu.log import FatalError
from multiverso_tpu.models import from_config, longcat
from multiverso_tpu.ops import held_expert_layer, route_topk

TOY = dict(
    model="longcat_flash", vocab_size=256, hidden_size=64,
    ffn_hidden_size=128, expert_ffn_hidden_size=32, num_layers=2,
    num_attention_heads=4, kv_lora_rank=16, q_lora_rank=32,
    qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, routed_scaling_factor=6,
    n_routed_experts=8, expert_offset=8, max_position_embeddings=512,
    rms_norm_eps=1e-5, rope_theta=1e7, zero_expert_num=16, moe_topk=6,
    dtype="float32",
    published={"n_routed_experts": 32, "num_layers": 28,
               "vocab_size": 131072})
# float32 on both sides, products in different orders
TOL = 2e-4


@pytest.fixture(scope="module")
def ref():
    return load_module("reference", "longcat-flash-ep32")


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("seed", [7, 2147483001])
def test_forward_matches_reference_logits(ref, seed):
    lm = from_config(TOY, seed)
    toks = np.random.default_rng(seed).integers(0, 256, 40).astype(np.int32)
    want = np.asarray(ref.logits(TOY, seed, [toks])[0])
    np.testing.assert_allclose(np.asarray(lm.logits(toks)), want, atol=TOL)
    assert want.std() > 0.5         # logits of a live model, not zeros


@pytest.mark.parametrize("fault", ["no_experts", "no_identity",
                                   "gates_unscaled", "no_mla_scale",
                                   "no_rope"])
def test_reference_faults_move_the_logits(ref, fault):
    """Each planted fault of the reference is far outside ``TOL``: the
    comparison above can see every part of the block."""
    toks = np.random.default_rng(3).integers(0, 256, 40).astype(np.int32)
    want = np.asarray(ref.logits(TOY, 7, [toks])[0])
    bad = np.asarray(ref.logits(TOY, 7, [toks], fault=fault)[0])
    assert np.abs(bad - want).max() > 0.1


# -- through the engine ---------------------------------------------------------
@pytest.fixture(scope="module")
def served(mv_session_module, ref):
    """Eight requests through InferenceServer -> DecodeEngine on a paged
    latent pool: 16-token chunks, 4-token blocks; the 7th repeats the
    4th's prompt (a full prefix hit: copy-on-write of the last block),
    the 8th shares the 3rd's first 24 tokens (a partial hit)."""
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
    return {"outs": outs, "gaps": gaps, "stats": stats, "eng": eng}


@pytest.fixture(scope="module")
def mv_session_module():
    import multiverso_tpu as mv

    mv.init(["test", "-log_level=error"])
    yield mv
    mv.shutdown()


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


def test_engine_used_the_prefix_cache_and_one_trace(served):
    s = served["stats"]
    assert s["prefix_hits"] > 0 and s["cow_copies"] == 1
    assert s["prefill_tokens_saved"] >= 40 + 24
    assert s["step_traces"] == 1 and s["prefill_traces"] == 1
    assert served["eng"].pool_drift() is None
    # one latent pool: 2 x 2 sublayers x 4 positions x a row of (16 + 8)
    # floats in whole 128-lane tiles
    assert s["kv_bytes_per_device"] == (s["kv_pool_blocks"] + 1) \
        * 4 * 4 * 128 * 4


def test_routing_counters_in_stats(served):
    """Design at the toy size: 6 picks over 32 + 16 outputs, 8 held."""
    s = served["stats"]
    assert s["moe_layer_tokens"] > 0
    assert abs(s["moe_ffn_picks_per_token"] - 4.0) < 0.4
    assert abs(s["moe_ffn_picks_per_token"]
               + s["moe_identity_picks_per_token"] - 6.0) < 1e-6
    assert abs(s["moe_held_pairs_per_token"] - 1.0) < 0.3
    assert 1.0 <= s["moe_held_load_max_over_mean"] < 4.0


# -- latent attention: the two forms ----------------------------------------------
def test_mla_latent_form_equals_expanded_form():
    cfg = longcat.config_from_dict(TOY, 5)
    w = longcat.init_params(cfg)["blocks"][0]["mla"][1]
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


# -- the expert layer -----------------------------------------------------------
def _layer_inputs(seed=0, T=24):
    u = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (T, TOY["hidden_size"])), jnp.float32)
    return u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True))


def _ref_layer(ref, cfg, u, bias=None, identity=True):
    w = ref.block_weights(cfg, 7, 0)
    if bias is not None:
        w["router_bias"] = jnp.asarray(bias, jnp.float32)
    return np.asarray(ref.expert_layer(cfg, ref._ops(""), w, u,
                                       identity=identity))


def _program_layer(cfg_dict, u, bias=None, identity=True):
    cfg = longcat.config_from_dict(cfg_dict, 7)
    blk = longcat.init_params(cfg)["blocks"][0]
    if bias is not None:
        blk["router_bias"] = jnp.asarray(bias, jnp.float32)
    y, counts = longcat.expert_layer(cfg, blk, u, identity=identity)
    return np.asarray(y), np.asarray(counts)


def test_shares_add_up_to_the_uncut_layer(ref):
    """The four shares' expert parts, plus the identity part ONCE, are
    the reference's uncut expert layer (all 32 FFN experts held)."""
    u = _layer_inputs()
    uncut = dict(TOY, n_routed_experts=32, expert_offset=0)
    want = _ref_layer(ref, uncut, u)
    total = np.zeros_like(want)
    for share in range(4):
        cfg = dict(TOY, expert_offset=8 * share)
        y, _ = _program_layer(cfg, u, identity=(share == 0))
        total += y
    np.testing.assert_allclose(total, want, atol=TOL)
    # and the program's own uncut layer
    np.testing.assert_allclose(_program_layer(uncut, u)[0], want, atol=TOL)


@pytest.mark.parametrize("picks", ["identity_only", "ffn_only", "mixed"])
def test_expert_picks(ref, picks):
    """A bias plants the picks: all six on identity experts, all six on
    held FFN experts, or the router's own mix."""
    u = _layer_inputs(1)
    bias = np.zeros(48, np.float32)
    if picks == "identity_only":
        bias[32:] = 10.0
    elif picks == "ffn_only":
        bias[8:16] = 10.0
    got, counts = _program_layer(TOY, u, bias)
    np.testing.assert_allclose(got, _ref_layer(ref, TOY, u, bias), atol=TOL)
    T = u.shape[0]
    ffn, ident, held = counts[1] / T, counts[2] / T, counts[3] / T
    assert ffn + ident == 6
    if picks == "identity_only":
        assert (ffn, ident, held) == (0, 6, 0)
    elif picks == "ffn_only":
        assert (ffn, ident, held) == (6, 0, 6)
    else:
        assert 0 < ident < 6 and 0 < held < ffn


def test_no_token_dropped_under_planted_imbalance(ref):
    """Every token on ONE held expert (and five absent ones): its load
    is the whole token count, and the result is the reference's."""
    u = _layer_inputs(2, T=40)
    bias = np.zeros(48, np.float32)
    bias[[11, 0, 1, 2, 3, 4]] = 10.0       # 11 is held (offset 8), 0-4 absent
    got, counts = _program_layer(TOY, u, bias)
    np.testing.assert_allclose(got, _ref_layer(ref, TOY, u, bias), atol=TOL)
    load = counts[4:]
    assert load[3] == 40 and load.sum() == 40 and counts[3] == 40
    assert np.abs(got).max() > 0.1


def test_route_topk_gates_are_scaled_and_not_renormalised():
    u = _layer_inputs(3)
    rw = jnp.asarray(np.random.default_rng(3).standard_normal((64, 48)),
                     jnp.float32) * 0.15
    idx, gates = route_topk(u, rw, jnp.zeros(48), 6, 6.0)
    p = jax.nn.softmax(u @ rw, -1)
    np.testing.assert_allclose(
        np.asarray(gates), 6.0 * np.sort(np.asarray(p), -1)[:, ::-1][:, :6],
        rtol=1e-5)
    y, _ = held_expert_layer(
        u, idx, gates, {"w_gate": jnp.zeros((2, 64, 8)),
                        "w_up": jnp.zeros((2, 64, 8)),
                        "w_down": jnp.zeros((2, 8, 64))}, 32, 0)
    ident = np.where(np.asarray(idx) >= 32, np.asarray(gates), 0).sum(-1)
    np.testing.assert_allclose(np.asarray(y), ident[:, None] * np.asarray(u),
                               atol=1e-5)


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


def test_kv_transfer_refused_and_weights_pinned_without_copy(mv_session):
    from multiverso_tpu.serving import InferenceServer

    lm = from_config(TOY, 7)
    srv = InferenceServer("t")
    eng = srv.register_decoder("lm", lm, slots=2, max_prompt=8, max_new=4,
                               kv_block_size=4, prefill_token_budget=4)
    eng.warmup()
    assert not eng.supports_transfer
    with pytest.raises(RuntimeError, match="KV transfer"):
        eng.submit_prefill(np.arange(4, dtype=np.int32))
    assert eng.splice({})["skipped"] == "unsupported"
    # the pinned weights ARE the model's (10 GB cannot exist twice)
    assert eng._pinned["embed"] is lm.params["embed"]
    assert eng._pinned["blocks"][0]["experts"]["w_up"] \
        is lm.params["blocks"][0]["experts"]["w_up"]
    # one pool of latent rows, [2 x blocks, N + 1, Bs, pool_width]: the
    # (16 + 8)-wide row padded to whole 128-lane tiles
    assert lm.config.cache_width == 24
    assert eng._pools[0].shape == (4, 2 * 3 + 1, 4, 128)


def test_from_config_builds_both_models(mv_session):
    from multiverso_tpu.models import LongCatLM, TransformerLM

    assert isinstance(from_config(TOY, 1), LongCatLM)
    lm = from_config(dict(model="transformer_lm", vocab_size=64, n_embd=32,
                          n_layer=1, n_head=2, n_inner=64, n_positions=16,
                          dtype="float32", learning_rate=0.1, momentum=0.9),
                     3, attention="reference")
    assert isinstance(lm, TransformerLM) and lm.config.seed == 3
    with pytest.raises(ValueError):
        from_config({"model": "nope"}, 0)
    # a setting nobody reads is an error, never dropped in silence
    with pytest.raises(TypeError, match="takes no overrides"):
        from_config(TOY, 1, attention="reference")
