"""The dots.vlm1 serving cell off the chip, at a toy size (CPU, float32):
what ``test_longcat_cell.py`` does for LongCat's cell, for
``serve_dotsvlm1_ep16_closed128`` (driver ``serve_closed_routed``:
``serve_closed_cfg``'s loop, the MEAN gap of the served tokens compared;
configuration kind ``deepseek_v3``), and what ``correct`` has to catch
in this model.

* the sound run through the harness's own ``run_cell`` is correct, books
  the routing counters and reports every metric the cell lists;
* the float8 control picks other tokens;
* planted faults, each broken UNDERNEATH the program while the harness
  runs as it is, each has to print ``correct: false``: the held experts
  dropped, the gates not normalised, the group limit ignored, the
  router's bias ignored, the shared expert dropped, YaRN's ``m^2`` left
  off the softmax scale, the rotary slice unrotated, the leading dense
  layer given experts;
* the reference's own planted faults (the driver's ``controls``, which
  ``benchmarks/limits.py --controls 1`` reads on the chip at the cell's
  own size) each read over the limit too.

The toy keeps the shapes that the faults live in: 8 groups of 4 experts
with 4 kept, a nonzero bias, a leading dense layer before two expert
layers, YaRN with its ramp inside the toy's four frequencies. It holds
16 of the 32 routed experts (the cell 16 of 256) so that a wrong pick
meets a held expert often enough to move a greedy token within a few
dozen served tokens, which is all that the comparison reads. A
router in bfloat16 is NOT among the faults, for the reason
``test_longcat_cell.py`` gives: ``tests/test_dsv3.py`` compares logits
and catches it.

Run: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``
"""

from __future__ import annotations

import dataclasses
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

CELL = "serve_dotsvlm1_ep16_closed128"
SHRINK = dict(
    # the configuration: every width a toy's, the share 16 of 32 experts
    vocab_size=256, hidden_size=64, intermediate_size=160,
    moe_intermediate_size=32, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, kv_lora_rank=16, q_lora_rank=32,
    qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
    n_routed_experts=16, expert_offset=0, num_experts_per_tok=4,
    max_position_embeddings=4096,
    rope_scaling=dict(type="yarn", factor=40, beta_fast=32, beta_slow=1,
                      mscale=1, mscale_all_dim=1,
                      original_max_position_embeddings=64),
    dtype="float32", published={"n_routed_experts": 32},
    # the traffic
    slots=4, clients=4, max_prompt=48, max_new=24, prompt_min=4,
    prompt_max=48, new_min=8, new_max=24, prefill_token_budget=16,
    requests=64, length_cycle=4, ramp_s=0.5, check_requests=8,
    # float32 on both sides: a sound run's served tokens are the
    # reference's own (every gap 0, or ~1e-6 on a tie)
    limits={"token_logit_gap_mean": 1e-5, "short_answers": 0},
    engine={"kv_block_size": 4})


def run(seed: int = 11, trace: bool = False):
    import jax

    with jax.default_matmul_precision("highest"):
        return harness.run_cell(CELL, seed, 1.0, trace, require_tpu=False,
                                shrink=SHRINK)


def failed(line) -> list:
    return [n for n, (v, lim) in line["compared"].items()
            if v is None or v > lim]


def test_sound_run_is_correct_and_books_the_routing_counters():
    line = run()
    assert line["correct"], line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0, line
    assert {"setup_s", "serve_tokens_per_s", "serve_req_p95_ms"} \
        <= set(line["metrics"]) or line["attempted"] < 20
    eng = line["info"]["engine"]
    # 4 picks over 32 outputs, 16 held: groups 0-3, of which a token
    # keeps none with probability C(4,4) / C(8,4) = 1/70
    assert abs(eng["moe_held_pairs_per_token"] - 2.0) < 0.5
    assert 0.9 < eng["moe_home_group_share"] <= 1.0
    assert 1.0 <= eng["moe_held_load_max_over_mean"] < 3.0
    assert eng["step_traces"] == 1 and eng["prefill_traces"] == 1


def test_traced_run_reports_the_counter_metrics():
    """Off the chip the trace holds no device program, so the two shares
    of a device time have nothing to read and are left out (never 0);
    the counter metrics, the share of the whole step and the engine's
    phases are there, and no other model's metric is."""
    got = run(trace=True)["metrics"]
    assert {"moe_held_pairs_per_token", "moe_home_group_share",
            "moe_held_load_max_over_mean", "dotsvlm1_serve_mfu",
            "engine_decode_step_ms", "engine_prefill_chunk_ms"} \
        <= set(got), sorted(got)
    assert not {"serve_mfu", "longcat_serve_mfu",
                "moe_ffn_picks_per_token"} & set(got)


def test_controls_each_read_over_the_limit():
    """The driver's own ``controls`` (what ``limits.py --controls 1``
    reads): the float8 control and every fault the reference plants, on
    sequences of random tokens, which themselves lie far below the best
    logit."""
    import jax

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx = harness.Ctx(bench, harness.find(bench["workloads"], CELL,
                                          "workload"), 11, 1, False, SHRINK)
    driver = harness.load_module("drivers", ctx.traffic["driver"])
    rng = np.random.default_rng(3)
    got = {"sequences": [rng.integers(0, 256, 64).astype(np.int32)
                         for _ in range(3)],
           "prompt_lens": [32] * 3, "short_answers": 0, "finished": 3}
    with jax.default_matmul_precision("highest"):
        sound = driver.check(got, ctx)
        upper = driver.controls(got, ctx)
    assert sound[0]["name"] == "token_logit_gap_mean" \
        and sound[0]["value"] > 1.0
    ref = harness.load_module("reference", ctx.config_name)
    assert set(upper) == {"control_float8_e4m3fn"} \
        | {f"fault_{f}" for f in ref.FAULTS}
    for name, rows in upper.items():
        assert rows[0]["value"] > rows[0]["limit"], (name, rows)


def test_work_counts_are_the_configuration_files():
    """``work_dsv3`` against the arithmetic written into the
    configuration's ``bytes`` (M = 1e6 parameters)."""
    from benchmarks import work_dsv3 as wd

    c = harness.load_json(harness.ROOT, "benchmarks", "configs",
                          "dots-vlm1-ep16.json")
    assert round(wd.mla_params(c) / 1e6, 2) == 187.11
    assert round(wd.dense_layer_params(c) / 1e6, 2) == 583.47
    assert round(wd.expert_layer_params_outside_routed(c) / 1e6, 2) == 232.98
    assert round(wd.decode_weight_bytes(c) / 1e9, 2) == 10.79
    assert wd.cache_row_bytes(c) == 6912 and wd.layers(c) == (1, 5)
    # a token that meets no held expert still pays MLA, router and the
    # shared expert; each held pair adds one expert
    assert wd.token_flops(c, 1.0) - wd.token_flops(c, 0.0) \
        == 2.0 * 5 * wd.expert_params(c)


def _config(**changes):
    return lambda real: lambda cfg, seed: dataclasses.replace(
        real(cfg, seed), **changes)


def _router(**kw):
    """``route_group_limited`` as the program's layer calls it, with
    arguments replaced by name."""
    import inspect

    def wrap(real):
        names = list(inspect.signature(real).parameters)

        def planted(*args):
            a = dict(zip(names, args))
            a.update({k: v(a) for k, v in kw.items()})
            return real(**a)
        return planted
    return wrap


def _raw_gates(real):
    import jax
    import jax.numpy as jnp

    def planted(u, router_w, router_bias, top_k, n_group, topk_group, scale):
        idx, _, kept = real(u, router_w, router_bias, top_k, n_group,
                            topk_group, scale)
        s = jax.nn.sigmoid(jnp.dot(u.astype(jnp.float32), router_w,
                                   precision=jax.lax.Precision.HIGHEST))
        return idx, scale * jnp.take_along_axis(s, idx, -1), kept
    return planted


def _no_routed(real):
    def planted(u, *args, **kw):
        y, counts = real(u, *args, **kw)
        return y * 0, counts
    return planted


def _no_shared(real):
    def planted(cfg):
        params = real(cfg)
        for layer in params["layers"]:
            if "shared" in layer:
                layer["shared"]["w_down"] = layer["shared"]["w_down"] * 0
        return params
    return planted


@pytest.mark.parametrize("module,name,wrap", [
    ("deepseek_v3", "held_expert_layer", _no_routed),
    ("deepseek_v3", "route_group_limited", _raw_gates),
    ("deepseek_v3", "route_group_limited",
     _router(topk_group=lambda a: a["n_group"])),
    ("deepseek_v3", "route_group_limited",
     _router(router_bias=lambda a: a["router_bias"] * 0)),
    ("deepseek_v3", "init_params", _no_shared),
    ("deepseek_v3", "config_from_dict", _config(first_k_dense_replace=0)),
    ("longcat", "rope", lambda real: lambda x, pos, inv: x),
    ("deepseek_v3", "softmax_divisor", None),
], ids=["held_experts_dropped", "gates_not_normalised",
        "group_limit_ignored", "router_bias_ignored",
        "shared_expert_dropped", "leading_layer_given_experts",
        "rotary_slice_unrotated", "yarn_mscale_left_off_the_scale"])
def test_planted_fault_is_not_correct(monkeypatch, module, name, wrap):
    from multiverso_tpu.models import deepseek_v3, longcat

    if wrap is None:
        monkeypatch.setattr(
            deepseek_v3.DeepSeekV3Config, name, property(
                lambda c: math.sqrt(c.qk_nope_head_dim
                                    + c.qk_rope_head_dim)))
    else:
        mod = {"deepseek_v3": deepseek_v3, "longcat": longcat}[module]
        monkeypatch.setattr(mod, name, wrap(getattr(mod, name)))
    line = run()
    assert not line["correct"] \
        and "token_logit_gap_mean" in failed(line), line["compared"]
