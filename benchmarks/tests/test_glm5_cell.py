"""The GLM-5 serving cell off the chip, at a toy size (CPU, float32):
what ``test_mellum2_cell.py`` does for Mellum's cell, for
``serve_glm5_ep16_closed16`` (driver ``serve_closed_sparse``:
``serve_closed_routed`` whole, the ``dsa_*`` counters and the selected
contexts kept; configuration kind ``glm_moe_dsa``), and what ``correct``
has to catch in this model.

* the sound run through the harness's own ``run_cell`` is correct, books
  the routing and the selection's counters and reports every metric the
  cell lists that can be read off the chip;
* planted faults, each broken UNDERNEATH the program while the harness
  runs as it is, each has to print ``correct: false``: the indexer
  ignored (every earlier position read), half the top-k, the top-k one
  position wider, the ReLU dropped, the indexer's rotary left off, the
  key LayerNorm dropped, the head weights ignored, the index keys of the
  neighbouring layer read, the held experts dropped, the gates not
  normalised, the shared expert dropped;
* the reference's own planted faults (the driver's ``controls``, which
  ``benchmarks/limits.py --controls 1`` reads on the chip at the cell's
  own size) and the float8 control each read over the limit too;
* the readers of a device time stay under their peaks, read the
  program's counts over the trace's own runs, and read nothing off a
  program without the counters;
* ``work_glm5`` is the arithmetic written into the configuration file.

The toy keeps the shapes that the faults live in: a leading dense layer
and two expert layers, a top-16 selection under prompts of 40-70 tokens
and answers of up to 24 (every request passes the top-k, so one position
more is a sixteenth of a query's keys), 4 index heads of 32 whose first 8
values turn, 16 of 32 routed experts held, top-4 in one group.

Run: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``
"""

from __future__ import annotations

import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402

CELL = "serve_glm5_ep16_closed16"
K = 16
SHRINK = dict(
    # the configuration: every width a toy's, the share 16 of 32 experts
    vocab_size=256, hidden_size=64, intermediate_size=160,
    moe_intermediate_size=32, num_hidden_layers=3, first_k_dense_replace=1,
    num_attention_heads=4, kv_lora_rank=16, q_lora_rank=32,
    qk_rope_head_dim=8, qk_nope_head_dim=24, v_head_dim=32, qk_head_dim=32,
    n_routed_experts=16, num_experts_per_tok=4, index_n_heads=4,
    index_head_dim=32, index_topk=K, max_position_embeddings=4096,
    dtype="float32", published={"n_routed_experts": 32},
    # the traffic
    slots=4, clients=4, max_prompt=70, max_new=24, prompt_min=40,
    prompt_max=70, new_min=8, new_max=24, prefill_token_budget=16,
    requests=64, length_cycle=4, ramp_s=0.5, check_requests=8,
    check_queries=4,
    # float32 on both sides: a sound run's served tokens are the
    # reference's own (every gap 0, or ~1e-6 on a tie) and so are its
    # selections (a position flips only on a tie of index scores); one
    # position more is 1 of 17 of a query's set
    limits={"token_logit_gap_mean": 1e-5, "short_answers": 0,
            "selection_mismatch": 0.01},
    engine={"kv_block_size": 4, "prefix_cache": False})


def run(seed: int = 11, trace: bool = False):
    import jax

    with jax.default_matmul_precision("highest"):
        return harness.run_cell(CELL, seed, 1.0, trace, require_tpu=False,
                                shrink=SHRINK)


def failed(line) -> list:
    return [n for n, (v, lim) in line["compared"].items()
            if v is None or v > lim]


def test_sound_run_is_correct_and_books_the_selections_counters():
    line = run()
    assert line["correct"], line["compared"]
    assert line["compared"]["selection_mismatch"][0] < 1e-3, line["compared"]
    assert line["info"]["checked"]["queries"] == 4 * 8
    assert line["attempted"] > 0 and line["failed"] == 0, line
    assert {"setup_s", "serve_tokens_per_s", "serve_req_p95_ms"} \
        <= set(line["metrics"]) or line["attempted"] < 20
    eng = line["info"]["engine"]
    # 4 picks over 32 outputs, 16 held: half of them
    assert abs(eng["moe_held_pairs_per_token"] - 2.0) < 0.5
    assert eng["dsa_step_runs"] > 0 and eng["dsa_chunk_runs"] > 0
    # contexts of 40-93 against a top-16
    assert 0.15 < eng["dsa_selected_share"] < 0.45
    assert eng["dsa_attended_positions"] \
        <= 3 * K * 4 * eng["dsa_step_runs"]
    assert eng["step_traces"] == 1 and eng["prefill_traces"] == 1
    k = line["info"]
    assert 0 < k["prefill_selected_context"] < k["prefill_context"]
    assert 0 < k["decode_selected_context"] < k["decode_context"]


def test_traced_run_reports_the_counter_metrics():
    """Off the chip the trace holds no device program, so the shares of
    a device time have nothing to read and are left out (never 0); the
    counter metrics, the share of the whole step and the engine's phases
    are there, and no other model's metric is."""
    got = run(trace=True)["metrics"]
    assert {"moe_held_pairs_per_token", "moe_held_load_max_over_mean",
            "glm5_serve_mfu", "dsa_selected_share", "engine_decode_step_ms",
            "engine_prefill_chunk_ms"} <= set(got), sorted(got)
    assert not {"serve_mfu", "dotsvlm1_serve_mfu", "moe_home_group_share",
                "swa_read_share", "dsa_index_step_roofline",
                "glm5_decode_step_roofline",
                "glm5_prefill_chunk_mfu"} & set(got)


def _ctx_with_a_trace(steps_in_trace: int, index_s: float):
    """The cell's own sizes, counters of a 51 s window (300 step runs,
    200 chunk runs) and a reduced trace holding ``steps_in_trace`` of the
    steps at 14 ms each, 40 chunks at 60 ms, and the indexer's kernel
    calls at ``index_s`` seconds in all."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx = harness.Ctx(bench, harness.find(bench["workloads"], CELL,
                                          "workload"), 11, 1, True)
    ctx.peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t = ctx.traffic
    live, context, runs = 15.5, 6000.0, 300
    ctx.counters.update(
        completed=60, prompt_tokens=60 * 5300.0, out_tokens=60 * 384.0,
        elapsed_s=51.0, prefill_context=60 * 5300 * 2650.0,
        prefill_selected_context=60 * 5300 * 1800.0,
        decode_context=60 * 384 * 5500.0,
        decode_selected_context=60 * 384 * 2048.0,
        engine={"moe_held_pairs_per_token": 0.5,
                "dsa_step_runs": runs, "dsa_chunk_runs": 200,
                "dsa_chunk_tokens": 200 * 480.0,
                "dsa_scored_positions": runs * 5 * live * context,
                "dsa_attended_positions": runs * 5 * live * 2048.0,
                "dsa_selected_share": 2048 / context})
    ctx.tracered = {
        "programs": {t["step_program"]: {"s": steps_in_trace * 0.014,
                                         "runs": steps_in_trace},
                     t["chunk_program"]: {"s": 40 * 0.060, "runs": 40}},
        # the indexer's kernel by its name; the step's fusions are none
        # of them
        "ops": {"dsa_index_scores.2 (f32[16,1,8704]) custom-call("
                "bf16[16,32,128], f32[16,32,128], bf16[13062,64,640])":
                index_s,
                "fusion.9 bf16[16,6144] fusion(bf16[16,6144])": 1.0}}
    return ctx


def test_device_readers_read_a_trace_and_stay_under_their_peaks():
    """The shares of a device time over a hand-made reduced trace at the
    cell's own sizes: a step at 14 ms and the indexer's calls at twice
    their least time read 50-90%, never over 100; a trace that holds a
    fifth of the window's steps reads what one holding them all reads
    (the counts are taken a run and multiplied by the trace's runs); a
    program without the counters reads nothing."""
    from benchmarks import work_glm5 as wg

    read = lambda ctx, name: harness.load_module("readers", name).read(ctx)
    probe = _ctx_with_a_trace(60, 1.0)
    eng = probe.counters["engine"]
    index_s = 2.0 * wg.index_key_bytes(probe.config) \
        * eng["dsa_scored_positions"] / eng["dsa_step_runs"] * 60 / 819e9
    ctx = _ctx_with_a_trace(60, index_s)
    step = read(ctx, "glm5_decode_step_roofline")
    assert 50 < step < 90, step
    assert abs(read(ctx, "dsa_index_step_roofline") - 50.0) < 1e-6
    assert 0 < read(ctx, "glm5_prefill_chunk_mfu") < 100
    assert 0 < read(ctx, "glm5_serve_mfu") < 100
    assert abs(read(ctx, "dsa_selected_share") - 2048 / 6000) < 1e-9
    whole = _ctx_with_a_trace(300, 5 * index_s)
    assert abs(read(whole, "glm5_decode_step_roofline") - step) < 1e-9
    assert abs(read(whole, "dsa_index_step_roofline") - 50.0) < 1e-6
    # a program without the counters (the parent): nothing to read
    ctx.counters["engine"] = {}
    for name in ("glm5_decode_step_roofline", "dsa_index_step_roofline",
                 "glm5_prefill_chunk_mfu", "glm5_serve_mfu",
                 "dsa_selected_share"):
        assert read(ctx, name) is None, name


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
    ref = harness.load_module("reference", ctx.config_name)
    rng = np.random.default_rng(3)
    got = {"sequences": [rng.integers(0, 256, 70).astype(np.int32)
                         for _ in range(3)],
           "prompt_lens": [40] * 3, "short_answers": 0, "finished": 3}
    got["queries"] = [driver.decode_queries(40, 70, 4)] * 3
    with jax.default_matmul_precision("highest"):
        sound = driver.check(got, ctx)
        upper = driver.controls(got, ctx)
    assert sound[0]["name"] == "token_logit_gap_mean" \
        and sound[0]["value"] > 1.0
    assert set(upper) == {"control_float8_e4m3fn"} \
        | {f"fault_{f}" for f in ref.FAULTS} and len(upper) == 12
    for name, rows in upper.items():
        assert rows[0]["value"] > rows[0]["limit"], (name, rows)
    # the selection: every fault of the indexer and the control move it
    # past the limit, the expert layers' faults through what the later
    # layers' indexers are fed
    assert sound[2]["name"] == "selection_mismatch" \
        and sound[2]["value"] is None
    for name, rows in upper.items():
        assert rows[2]["value"] > rows[2]["limit"], (name, rows)


def test_work_counts_are_the_configuration_files():
    """``work_glm5`` against the arithmetic written into the
    configuration's ``bytes`` (M = 1e6 parameters)."""
    from benchmarks import work_glm5 as wg
    from benchmarks.work_longcat import head_params, mla_params

    c = harness.load_json(harness.ROOT, "benchmarks", "configs",
                          "glm5-ep16.json")
    assert round(mla_params(c) / 1e6, 2) == 165.02
    assert round(wg.indexer_params(c) / 1e6, 2) == 9.37
    assert round(wg.dense_layer_params(c) / 1e6, 2) == 400.88
    assert round(wg.expert_layer_params_outside_routed(c) / 1e6, 2) \
        == 213.71
    assert round(head_params(c) / 1e6, 2) == 118.95
    assert round(wg.decode_weight_bytes(c) / 1e9, 2) == 7.59
    assert wg.layers(c) == (1, 4)
    assert wg.index_key_bytes(c) == 256 and wg.latent_row_bytes(c) == 1280
    # a token that meets no held expert still pays attention, the
    # indexer, the router and the shared expert; each held pair adds one
    # expert
    assert wg.token_flops(c, 1.0) - wg.token_flops(c, 0.0) \
        == 2.0 * 4 * wg.expert_params(c)
    assert wg.index_flops_per_pair(c) == 2 * 32 * 128


def test_driver_counts_selected_context_and_selection_mismatch():
    """The driver's own arithmetic: the context a top-k selection leaves
    queries, the decode queries it samples, and the mismatch of two
    selections given as positions or as masks."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx = harness.Ctx(bench, harness.find(bench["workloads"], CELL,
                                          "workload"), 11, 1, False)
    d = harness.load_module("drivers", ctx.traffic["driver"])
    assert d.selected_context(1, 3000, 2048) == 2048 * 2049 / 2 \
        + (3000 - 2048) * 2048
    assert d.selected_context(5, 4, 2048) == 0.0
    assert d.decode_queries(100, 120, 3).tolist() == [100, 109, 118]
    assert d.decode_queries(100, 101, 2).tolist() == [100, 100]
    ref = np.zeros((1, 2, 8), bool)
    ref[0, 0, [0, 1, 2]] = True
    ref[0, 1, [3, 4, 5]] = True
    same = np.array([[[2, 1, 0, -1], [5, 3, 4, -1]]])
    assert d.selection_mismatch([same], [ref]) == 0.0
    # one of three swapped in one query: 1 - 5 / 7
    moved = np.array([[[2, 1, 7, -1], [5, 3, 4, -1]]])
    assert abs(d.selection_mismatch([moved], [ref]) - 2 / 7) < 1e-12
    # every earlier position read (a mask): 1 - 6 / 9
    dense = np.tril(np.ones((8, 8), bool))[[2, 5]][None]
    assert abs(d.selection_mismatch([dense], [ref]) - 1 / 3) < 1e-12
    assert d.selection_mismatch(None, [ref]) is None


# -- planted faults, in the program -------------------------------------------
def _config(**changes):
    return lambda real: lambda cfg, seed: dataclasses.replace(
        real(cfg, seed), **changes)


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


def _no_relu(real):
    import jax.numpy as jnp

    def planted(q, w, keys):
        s = jnp.einsum("cjd,td->cjt", q, keys,
                       preferred_element_type=jnp.float32)
        return jnp.sum(w[:, :, None] * s, axis=1)
    return planted


def _no_routed(real):
    def planted(u, *args, **kw):
        y, counts = real(u, *args, **kw)
        return y * 0, counts
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


def _no_shared(real):
    def planted(cfg):
        params = real(cfg)
        for layer in params["layers"]:
            if "shared" in layer:
                layer["shared"]["w_down"] = layer["shared"]["w_down"] * 0
        return params
    return planted


@pytest.mark.parametrize("module,name,wrap", [
    ("glm5", "config_from_dict", _config(index_topk=1 << 20)),
    ("glm5", "config_from_dict", _config(index_topk=K // 2)),
    ("glm5", "config_from_dict", _config(index_topk=K + 1)),
    ("glm5", "index_scores", _no_relu),
    ("glm5", "rope", lambda real: lambda x, pos, inv: x),
    ("glm5", "layer_norm", lambda real: lambda x, gain, bias, eps: x),
    ("glm5", "index_scores",
     _args(w=lambda a: a["w"] * 0 + 1)),
    ("glm5", "index_keys",
     _args(l=lambda a: (a["l"] + 1) % a["cfg"].num_hidden_layers)),
    ("deepseek_v3", "held_expert_layer", _no_routed),
    ("deepseek_v3", "route_group_limited", _raw_gates),
    ("glm5", "init_params", _no_shared),
], ids=["indexer_ignored", "topk_half", "topk_one_wider", "relu_dropped",
        "indexer_rotary_left_off", "key_norm_dropped", "weights_ignored",
        "neighbouring_layers_keys", "held_experts_dropped",
        "gates_not_normalised", "shared_expert_dropped"])
def test_planted_fault_is_not_correct(monkeypatch, module, name, wrap):
    from multiverso_tpu.models import deepseek_v3, glm5

    mod = {"glm5": glm5, "deepseek_v3": deepseek_v3}[module]
    monkeypatch.setattr(mod, name, wrap(getattr(mod, name)))
    line = run()
    assert not line["correct"] \
        and "token_logit_gap_mean" in failed(line), line["compared"]
    if module == "glm5" and name != "init_params":
        assert "selection_mismatch" in failed(line), line["compared"]
