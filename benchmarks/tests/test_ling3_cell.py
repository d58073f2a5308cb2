"""The Ling-3.0-flash serving cell off the chip, at a toy size (CPU,
float32): what ``test_dotsvlm1_cell.py`` does for dots.vlm1's cell, for
``serve_ling3_ep4_closed128`` (driver ``serve_closed_hybrid``:
``serve_closed_routed`` whole, and the ``kda_*`` counters kept;
configuration kind ``bailing_hybrid``), and what ``correct`` has to
catch in this model.

* the sound run through the harness's own ``run_cell`` is correct, books
  the routing and the KDA counters and reports every metric the cell
  lists that can be read off the chip;
* planted faults, each broken UNDERNEATH the program while the harness
  runs as it is, each has to print ``correct: false``: the twelve that
  the reference plants for the chip's controls, planted here in the
  program by name;
* the reference's own planted faults (the driver's ``controls``, which
  ``benchmarks/limits.py --controls 1`` reads on the chip at the cell's
  own size) and the float8 control each read over the limit too;
* ``work_ling3`` is the arithmetic written into the configuration file.

The toy keeps the shapes that the faults live in: seven layers in the
published pattern (five KDA, one MLA, one KDA; the first dense), 4 taps,
prompts of up to three 16-token chunks so that a state and a conv tail
cross chunk boundaries, 8 groups of 4 experts with 4 kept and a nonzero
bias. It holds 16 of the 32 routed experts so that a wrong pick meets a
held expert often enough to move a greedy token within a few dozen
served tokens.

Run: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

CELL = "serve_ling3_ep4_closed128"
SHRINK = dict(
    # the configuration: every width a toy's, the share 16 of 32 experts
    vocab_size=256, hidden_size=64, intermediate_size=160,
    moe_intermediate_size=32, num_attention_heads=4, head_dim=16,
    kv_lora_rank=16, qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
    num_experts=16, expert_offset=0, num_experts_per_tok=4,
    max_position_embeddings=4096, dtype="float32",
    published={"num_experts": 32},
    # the traffic
    slots=4, clients=4, max_prompt=48, max_new=24, prompt_min=4,
    prompt_max=48, new_min=8, new_max=24, prefill_token_budget=16,
    requests=64, length_cycle=4, ramp_s=0.5, check_requests=8,
    # float32 on both sides: a sound run's served tokens are the
    # reference's own (every gap 0, or ~1e-6 on a tie)
    limits={"token_logit_gap_mean": 1e-5, "short_answers": 0},
    engine={"kv_block_size": 4, "prefix_cache": False})


def run(seed: int = 11, trace: bool = False):
    import jax

    with jax.default_matmul_precision("highest"):
        return harness.run_cell(CELL, seed, 1.0, trace, require_tpu=False,
                                shrink=SHRINK)


def failed(line) -> list:
    return [n for n, (v, lim) in line["compared"].items()
            if v is None or v > lim]


def test_sound_run_is_correct_and_books_both_kinds_of_counter():
    line = run()
    assert line["correct"], line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0, line
    assert {"setup_s", "serve_tokens_per_s", "serve_req_p95_ms"} \
        <= set(line["metrics"]) or line["attempted"] < 20
    eng = line["info"]["engine"]
    assert abs(eng["moe_held_pairs_per_token"] - 2.0) < 0.5
    assert 0.9 < eng["moe_home_group_share"] <= 1.0
    assert -0.2 < eng["kda_mean_log_decay"] < -0.01
    # a reset an admission: every request the window's engine admitted
    assert eng["kda_state_resets"] >= line["attempted"]
    assert eng["kda_layer_tokens"] > 6 * eng["prefill_tokens"]
    assert eng["step_traces"] == 1 and eng["prefill_traces"] == 1


def test_traced_run_reports_the_counter_metrics():
    """Off the chip the trace holds no device program, so the shares of
    a device time have nothing to read and are left out (never 0); the
    counter metrics, the share of the whole step and the engine's phases
    are there, and no other model's metric is."""
    got = run(trace=True)["metrics"]
    assert {"moe_held_pairs_per_token", "moe_home_group_share",
            "moe_held_load_max_over_mean", "ling3_serve_mfu",
            "kda_mean_log_decay", "engine_decode_step_ms",
            "engine_prefill_chunk_ms"} <= set(got), sorted(got)
    assert not {"serve_mfu", "longcat_serve_mfu", "dotsvlm1_serve_mfu",
                "moe_ffn_picks_per_token", "kda_step_roofline",
                "ling3_decode_step_roofline"} & set(got)


def test_device_readers_read_a_trace_and_stay_under_their_peaks():
    """The four shares of a device time over a hand-made reduced trace at
    the cell's own sizes: a step at the compiler's 28 ms and KDA ops at
    twice their least time read 50-60%, never over 100."""
    from benchmarks import work_ling3 as wl

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx = harness.Ctx(bench, harness.find(bench["workloads"], CELL,
                                          "workload"), 11, 1, True)
    ctx.peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    c, t = ctx.config, ctx.traffic
    steps, chunks, live = 100, 30, 127
    decoded = steps * live
    ctx.counters.update(
        completed=50, prompt_tokens=50 * 400.0, out_tokens=50 * 512.0,
        elapsed_s=4.0, prefill_context=50 * 400 * 200.0,
        decode_context=decoded * 700.0,
        engine={"moe_held_pairs_per_token": 2.0, "prefill_tokens": 12000,
                "kda_layer_tokens": 6.0 * (decoded + 12000)})
    state_s = 2.0 * wl.slot_state_bytes(c) * decoded / 819e9
    ctx.tracered = {
        "programs": {t["step_program"]: {"s": steps * 0.028, "runs": steps},
                     t["chunk_program"]: {"s": chunks * 0.045,
                                          "runs": chunks}},
        # the kernel by its name, the fallback's fusions by the pool's
        # and a per-slot vector's shapes; the chunk's write of ONE
        # slot's state into the pool belongs to neither
        "ops": {"kda_step_pool.3 (f32[128,32,128], f32[6,128,32,128,128]) "
                "custom-call(f32[128,2,128,80], f32[128,32,128], "
                "f32[6,128,32,128,128])": state_s,
                "select_dynamic-update-slice_fusion.2 f32[6,128,32,128,128] "
                "fusion(f32[6,128,32,128,128], f32[128,32,128], pred[128])":
                state_s,
                "dynamic-update-slice.4 f32[6,128,32,128,128] "
                "dynamic-update-slice(f32[6,128,32,128,128], "
                "f32[1,1,32,128,128])": 7.0,
                "multiply_reduce_fusion.2 f32[8,32,64,64] "
                "fusion(f32[8,32,64,128])": 0.03,
                "fusion.7 f32[32,64,128] fusion(f32[32,64,64])": 0.02,
                "fusion.9 bf16[128,2560] fusion(bf16[128,2560])": 1.0}}
    read = lambda name: harness.load_module("readers", name).read(ctx)
    assert 55 < read("ling3_decode_step_roofline") < 65
    assert abs(read("kda_step_roofline") - 50.0) < 1e-6
    assert 0 < read("kda_chunk_roofline") < 100
    assert 0 < read("ling3_prefill_chunk_mfu") < 100
    # a program without the counters (the parent): nothing to read
    ctx.counters["engine"] = {}
    for name in ("ling3_decode_step_roofline", "kda_step_roofline",
                 "kda_chunk_roofline", "ling3_prefill_chunk_mfu",
                 "ling3_serve_mfu", "kda_mean_log_decay"):
        assert read(name) is None, name


def test_controls_each_read_over_the_limit(monkeypatch):
    """The driver's own ``controls`` (what ``limits.py --controls 1``
    reads): the float8 control and every fault the reference plants, on
    sequences of random tokens, which themselves lie far below the best
    logit."""
    import jax

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx = harness.Ctx(bench, harness.find(bench["workloads"], CELL,
                                          "workload"), 11, 1, False, SHRINK)
    driver = harness.load_module("drivers", ctx.traffic["driver"])
    ref = harness.load_module("reference", ctx.config_name)
    monkeypatch.setattr(ref, "FAULT_CHUNK", 16)
    rng = np.random.default_rng(3)
    got = {"sequences": [rng.integers(0, 256, 64).astype(np.int32)
                         for _ in range(3)],
           "prompt_lens": [40] * 3, "short_answers": 0, "finished": 3}
    with jax.default_matmul_precision("highest"):
        sound = driver.check(got, ctx)
        upper = driver.controls(got, ctx)
    assert sound[0]["name"] == "token_logit_gap_mean" \
        and sound[0]["value"] > 1.0
    assert set(upper) == {"control_float8_e4m3fn"} \
        | {f"fault_{f}" for f in ref.FAULTS} and len(upper) == 13
    for name, rows in upper.items():
        assert rows[0]["value"] > rows[0]["limit"], (name, rows)


def test_work_counts_are_the_configuration_files():
    """``work_ling3`` against the arithmetic written into the
    configuration's ``bytes`` (M = 1e6 parameters)."""
    from benchmarks import work_ling3 as wl

    c = harness.load_json(harness.ROOT, "benchmarks", "configs",
                          "ling3-flash-ep4.json")
    assert wl.layer_kinds(c) == (6, 1) and wl.ffn_kinds(c) == (1, 6)
    assert round(wl.kda_params(c) / 1e6, 2) == 63.0     # + 0.05M of taps
    assert round(wl.mla_params(c) / 1e6, 2) == 31.97
    assert round(wl.expert_params(c) / 1e6, 3) == 5.898
    assert round(wl.decode_weight_bytes(c) / 1e9, 2) == 10.28
    assert wl.cache_row_bytes(c) == 1152
    assert round(wl.slot_state_bytes(c) / 1e6, 2) == 13.03  # 12.58 + 0.44
    assert round(128 * wl.slot_state_bytes(c) / 1e9, 2) == 1.67
    # a token that meets no held expert still pays attention, router and
    # the shared expert; each held pair adds one expert
    assert wl.token_flops(c, 1.0) - wl.token_flops(c, 0.0) \
        == 2.0 * 6 * wl.expert_params(c)


# -- planted faults: the reference's twelve, planted in the program -------------
def _args(**kw):
    """The program's function with arguments replaced by name."""
    import inspect

    def wrap(real):
        names = list(inspect.signature(real).parameters)

        def planted(*args, **kwargs):
            a = dict(zip(names, args), **kwargs)
            a.update({k: v(a) for k, v in kw.items()})
            return real(**a)
        return planted
    return wrap


def _decay_ignored(real):
    def planted(cfg, w, x):
        qkv, log_a, b = real(cfg, w, x)
        return qkv, log_a * 0, b
    return planted


def _beta_one(real):
    def planted(cfg, w, x):
        qkv, log_a, b = real(cfg, w, x)
        return qkv, log_a, b * 0 + 1
    return planted


def _unnormalised(real):
    import jax
    import jax.numpy as jnp

    def planted(cfg, y):
        H, dh = cfg.num_attention_heads, cfg.head_dim
        q, k, v = jnp.split(jax.nn.silu(y).reshape(y.shape[0], 3 * H, dh),
                            3, axis=1)
        return q * dh ** -0.5, k, v
    return planted


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
    ("kda", "kda_chunk", _args(state=lambda a: a["state"] * 0)),
    ("ling", "kda_project", _decay_ignored),
    ("ling", "kda_project", _beta_one),
    ("kda", "short_conv_chunk", _args(tail=lambda a: a["tail"] * 0)),
    ("ling", "kda_heads", _unnormalised),
    ("longcat", "head_gate", lambda real: lambda cfg, w, x: None),
    ("longcat", "rope", lambda real: lambda x, pos, inv: x),
    ("deepseek_v3", "held_expert_layer", _no_routed),
    ("deepseek_v3", "route_group_limited", _raw_gates),
    ("deepseek_v3", "route_group_limited",
     _args(topk_group=lambda a: a["n_group"])),
    ("deepseek_v3", "route_group_limited",
     _args(router_bias=lambda a: a["router_bias"] * 0)),
    ("ling", "init_params", _no_shared),
], ids=["kda_state_dropped", "kda_decay_ignored", "kda_beta_one",
        "conv_tail_dropped", "kda_qk_unnormalised", "mla_gate_dropped",
        "rotary_unrotated", "held_experts_dropped", "gates_not_normalised",
        "group_limit_ignored", "router_bias_ignored",
        "shared_expert_dropped"])
def test_planted_fault_is_not_correct(monkeypatch, module, name, wrap):
    from multiverso_tpu.models import deepseek_v3, ling, longcat
    from multiverso_tpu.ops import kda

    mod = {"kda": kda, "ling": ling, "longcat": longcat,
           "deepseek_v3": deepseek_v3}[module]
    monkeypatch.setattr(mod, name, wrap(getattr(mod, name)))
    line = run()
    assert not line["correct"] \
        and "token_logit_gap_mean" in failed(line), line["compared"]
