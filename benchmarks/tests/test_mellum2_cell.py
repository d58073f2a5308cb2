"""The Mellum2 serving cell off the chip, at a toy size (CPU, float32):
what ``test_ling3_cell.py`` does for Ling's cell, for
``serve_mellum2_closed128`` (driver ``serve_closed_window``:
``serve_closed_routed`` whole, the ``swa_*`` counters and the
window-capped contexts kept; configuration kind ``mellum``), and what
``correct`` has to catch in this model.

* the sound run through the harness's own ``run_cell`` is correct, books
  the routing and the window's counters and reports every metric the
  cell lists that can be read off the chip;
* planted faults, each broken UNDERNEATH the program while the harness
  runs as it is, each has to print ``correct: false``: the eight that the
  reference plants for the chip's controls, planted here in the program
  by name, and a chunk that writes its ring rows before it attends;
* the reference's own planted faults (the driver's ``controls``, which
  ``benchmarks/limits.py --controls 1`` reads on the chip at the cell's
  own size) and the float8 control each read over the limit too;
* the readers of a device time stay under their peaks, and read nothing
  off a program without the counters;
* ``work_mellum2`` is the arithmetic written into the configuration file.

The toy keeps the shapes that the faults live in: three sliding layers
to one full one, 8 query heads over 2 KV heads, a window of 16 under
16-token chunks (prompts of up to 48 and answers of up to 24 run past
it, so a window one too wide is a sixteenth of the keys), YaRN with
``original_max_position_embeddings`` 32 so that its frequencies and
factor bite, 8 experts top-2.

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

CELL = "serve_mellum2_closed128"
W = 16
SHRINK = dict(
    # the configuration: every width a toy's, the layers' pattern and
    # the rotary kinds as published
    vocab_size=160, hidden_size=48, num_hidden_layers=4,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=24,
    sliding_window=W, max_position_embeddings=4096, dtype="float32",
    rope_parameters={
        "full_attention": {"rope_type": "yarn", "rope_theta": 10000,
                           "factor": 4,
                           "original_max_position_embeddings": 32,
                           "beta_fast": 32, "beta_slow": 1},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    # the traffic
    slots=4, clients=4, max_prompt=48, max_new=24, prompt_min=4,
    prompt_max=48, new_min=8, new_max=24, prefill_token_budget=16,
    requests=64, length_cycle=4, ramp_s=0.5, check_requests=8,
    # float32 on both sides: a sound run's served tokens are the
    # reference's own (every gap 0, or ~1e-6 on a tie)
    limits={"token_logit_gap_mean": 1e-5, "short_answers": 0},
    engine={"kv_block_size": 8, "prefix_cache": False})


def run(seed: int = 11, trace: bool = False):
    import jax

    with jax.default_matmul_precision("highest"):
        return harness.run_cell(CELL, seed, 1.0, trace, require_tpu=False,
                                shrink=SHRINK)


def failed(line) -> list:
    return [n for n, (v, lim) in line["compared"].items()
            if v is None or v > lim]


def test_sound_run_is_correct_and_books_the_windows_counters():
    line = run()
    assert line["correct"], line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0, line
    assert {"setup_s", "serve_tokens_per_s", "serve_req_p95_ms"} \
        <= set(line["metrics"]) or line["attempted"] < 20
    eng = line["info"]["engine"]
    assert eng["moe_held_pairs_per_token"] == 2.0
    assert eng["swa_step_tokens"] > 0
    assert 0.2 < eng["swa_read_share"] < 1.0
    assert eng["swa_sliding_positions"] <= W * eng["swa_step_tokens"]
    assert eng["step_traces"] == 1 and eng["prefill_traces"] == 1
    k = line["info"]
    assert 0 < k["prefill_window_context"] < k["prefill_context"]
    assert 0 < k["decode_window_context"] < k["decode_context"]


def test_traced_run_reports_the_counter_metrics():
    """Off the chip the trace holds no device program, so the shares of
    a device time have nothing to read and are left out (never 0); the
    counter metrics, the share of the whole step and the engine's phases
    are there, and no other model's metric is."""
    got = run(trace=True)["metrics"]
    assert {"moe_held_pairs_per_token", "moe_held_load_max_over_mean",
            "mellum2_serve_mfu", "swa_read_share", "engine_decode_step_ms",
            "engine_prefill_chunk_ms"} <= set(got), sorted(got)
    assert not {"serve_mfu", "ling3_serve_mfu", "moe_home_group_share",
                "kda_mean_log_decay", "swa_step_roofline",
                "mellum2_decode_step_roofline",
                "mellum2_prefill_chunk_mfu"} & set(got)


def test_device_readers_read_a_trace_and_stay_under_their_peaks():
    """The three shares of a device time over a hand-made reduced trace
    at the cell's own sizes: a step at 16 ms and the sliding layers'
    kernel calls at twice their least time read 50-80%, never over 100;
    a program without the counters reads nothing."""
    from benchmarks import work_mellum2 as wm

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx = harness.Ctx(bench, harness.find(bench["workloads"], CELL,
                                          "workload"), 11, 1, True)
    ctx.peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    c, t = ctx.config, ctx.traffic
    steps, chunks, live = 100, 60, 127
    sliding = steps * live * 1024.0
    ring_s = 2.0 * wm.cache_row_bytes(c) * 6 * sliding / 819e9
    ctx.counters.update(
        completed=50, prompt_tokens=50 * 2000.0, out_tokens=50 * 768.0,
        elapsed_s=4.0, prefill_context=50 * 2000 * 1000.0,
        prefill_window_context=50 * 2000 * 800.0,
        decode_context=50 * 768 * 2400.0,
        decode_window_context=50 * 768 * 1024.0,
        engine={"moe_held_pairs_per_token": 8.0, "prefill_tokens": 30000,
                "swa_sliding_positions": sliding,
                "swa_full_positions": steps * live * 2250.0,
                "swa_read_share": 1024 / 2250})
    ctx.tracered = {
        "programs": {t["step_program"]: {"s": steps * 0.016, "runs": steps},
                     t["chunk_program"]: {"s": chunks * 0.012,
                                          "runs": chunks}},
        # the sliding layers' kernel by its name; the full layers' calls
        # and the step's fusions belong to none of them
        "ops": {"swa_paged_attention.3 (f32[128,32,512]) custom-call("
                "bf16[128,32,1024], bf16[1536,512,1024])": ring_s,
                "paged_mq_attention.1 (f32[128,32,512]) custom-call("
                "bf16[128,32,1024], bf16[65538,16,1024])": 1.0,
                "fusion.9 bf16[128,2304] fusion(bf16[128,2304])": 1.0}}
    read = lambda name: harness.load_module("readers", name).read(ctx)
    assert 60 < read("mellum2_decode_step_roofline") < 80
    assert abs(read("swa_step_roofline") - 50.0) < 1e-6
    assert 0 < read("mellum2_prefill_chunk_mfu") < 100
    assert 0 < read("mellum2_serve_mfu") < 100
    assert abs(read("swa_read_share") - 0.455) < 0.01
    # a program without the counters (the parent): nothing to read
    ctx.counters["engine"] = {}
    for name in ("mellum2_decode_step_roofline", "swa_step_roofline",
                 "mellum2_prefill_chunk_mfu", "mellum2_serve_mfu",
                 "swa_read_share"):
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
    rng = np.random.default_rng(3)
    got = {"sequences": [rng.integers(0, 160, 64).astype(np.int32)
                         for _ in range(3)],
           "prompt_lens": [40] * 3, "short_answers": 0, "finished": 3}
    with jax.default_matmul_precision("highest"):
        sound = driver.check(got, ctx)
        upper = driver.controls(got, ctx)
    assert sound[0]["name"] == "token_logit_gap_mean" \
        and sound[0]["value"] > 1.0
    assert set(upper) == {"control_float8_e4m3fn"} \
        | {f"fault_{f}" for f in ref.FAULTS} and len(upper) == 9
    for name, rows in upper.items():
        assert rows[0]["value"] > rows[0]["limit"], (name, rows)


def test_work_counts_are_the_configuration_files():
    """``work_mellum2`` against the arithmetic written into the
    configuration's ``bytes`` (M = 1e6 parameters)."""
    from benchmarks import work_mellum2 as wm

    c = harness.load_json(harness.ROOT, "benchmarks", "configs",
                          "mellum2-12b-l8.json")
    assert wm.layer_kinds(c) == (6, 2)
    assert round(wm.attention_params(c) / 1e6, 3) == 21.234
    assert round(wm.expert_params(c) / 1e6, 3) == 6.193
    assert round(wm.decode_weight_bytes(c) / 1e9, 2) == 7.14
    assert wm.cache_row_bytes(c) == 2048
    # a ring a slot: 6 layers x 1,024 rows
    assert round(6 * 1024 * wm.cache_row_bytes(c) / 1e6, 2) == 12.58
    assert wm.window_context(1, 3000, 1024) == 1024 * 1025 / 2 \
        + (3000 - 1024) * 1024
    assert wm.token_flops(c, 1.0) - wm.token_flops(c, 0.0) \
        == 2.0 * 8 * wm.expert_params(c)


# -- planted faults: the reference's eight and one more, in the program --------
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


def _window(width):
    """The configuration's window replaced: a program that reads
    ``width`` positions where the model reads ``sliding_window``."""
    def wrap(real):
        def planted(cfg, seed):
            return real(dict(cfg, sliding_window=width), seed)
        return planted
    return wrap


def _rotary(full_freqs=None, full_scale=None, sliding_as_full=False):
    def wrap(real):
        def planted(cfg, full):
            if not full:
                return real(cfg, True) if sliding_as_full else real(cfg, False)
            freqs, scale = real(cfg, True)
            if full_freqs:
                freqs = real(cfg, False)[0]
            return freqs, scale if full_scale is None else full_scale
        return planted
    return wrap


def _mod_groups(real):
    """Head ``h`` reads KV head ``h % KV`` in place of ``h // (H / KV)``:
    the heads' queries permuted into the wrong groups and the outputs
    permuted back before ``W_o``."""
    def planted(cfg, w, q, rows, mask):
        H, KV = cfg.num_attention_heads, cfg.num_key_value_heads
        G = H // KV
        h = np.arange(H)
        # slot g * G + i of the grouped layout holds the head with
        # h % KV == g, the i-th of them
        order = np.concatenate([h[h % KV == g] for g in range(KV)])
        inv = np.argsort(order)
        wo = w["w_o"].reshape(H, -1, w["w_o"].shape[-1])
        return real(cfg, dict(w, w_o=wo[order].reshape(w["w_o"].shape)),
                    q[:, order], rows, mask)
    return planted


def _ring_written_first(real):
    """A chunk that writes its rows into the ring BEFORE attending: the
    ring part of a sliding layer's keys already holds the chunk's own
    rows where positions a window back should be."""
    import jax.numpy as jnp

    def planted(cfg, w, q, rows, mask):
        C, Wn = q.shape[0], cfg.sliding_window
        if rows.shape[0] != Wn + C:         # a full layer's view
            return real(cfg, w, q, rows, mask)
        ring, new = rows[:Wn], rows[Wn:]
        ring = ring.at[jnp.arange(C) % Wn].set(new)
        return real(cfg, w, q, jnp.concatenate([ring, new]), mask)
    return planted


def _no_experts(real):
    def planted(u, *args, **kw):
        y, counts = real(u, *args, **kw)
        return y * 0, counts
    return planted


@pytest.mark.parametrize("name,wrap", [
    ("config_from_dict", _window(1 << 12)),
    ("config_from_dict", _window(W + 1)),
    ("rotary", _rotary(full_scale=1.0)),
    ("rotary", _rotary(sliding_as_full=True)),
    ("rotary", _rotary(full_freqs=True)),
    ("route_topk", _args(normalize=lambda a: False)),
    ("attend_rows", _mod_groups),
    ("held_expert_layer", _no_experts),
    ("attend_rows", _ring_written_first),
], ids=["window_ignored", "window_off_by_one", "yarn_factor_dropped",
        "yarn_on_sliding", "full_default_frequencies",
        "gates_not_normalised", "gqa_mod_groups", "experts_dropped",
        "ring_written_before_the_chunk_attends"])
def test_planted_fault_is_not_correct(monkeypatch, name, wrap):
    from multiverso_tpu.models import mellum

    monkeypatch.setattr(mellum, name, wrap(getattr(mellum, name)))
    line = run()
    assert not line["correct"] \
        and "token_logit_gap_mean" in failed(line), line["compared"]
