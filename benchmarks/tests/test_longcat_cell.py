"""The LongCat serving cell off the chip, at a toy size (CPU, float32):
what ``selfcheck.py``'s walk does for the drivers it lists, for driver
``serve_closed_cfg`` (the walk's table of cells is not this PR's to
edit), and what ``correct`` has to catch in this model.

* the sound run through the harness's own ``run_cell`` is correct, books
  the routing counters and reports every metric the cell lists;
* the float8 control picks other tokens;
* planted faults, each broken UNDERNEATH the program while the harness
  runs as it is, each has to print ``correct: false``: the expert path
  dropped, identity experts skipped, gates not scaled by
  ``routed_scaling_factor``, an ``mla_scale_*`` factor left out, the
  rotary slice unrotated;
* the router's product in bfloat16 where the model says float32 is NOT
  among them, because the limits cannot see it: a 6th-against-7th pick
  that flips on the rounding moves a logit by one small gate and hardly
  ever the greedy token, which is all that ``token_logit_gap`` reads (at
  the cell's own size every activation is bfloat16 besides, and such a
  flip is the limit's own reason). Its control is the reference with
  only the router's product rounded (``router_compute``): it moves the
  LOGITS past the tolerance of ``tests/test_longcat.py``, which compares
  logits and is where that fault is caught.

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

CELL = "serve_longcat_ep32_closed128"
SHRINK = dict(
    # the configuration: every width a toy's, the share 8 of 32 experts
    vocab_size=256, hidden_size=64, ffn_hidden_size=128,
    expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
    kv_lora_rank=16, q_lora_rank=32, qk_rope_head_dim=8, v_head_dim=16,
    qk_nope_head_dim=16, n_routed_experts=8, expert_offset=8,
    zero_expert_num=16, moe_topk=6, max_position_embeddings=128,
    dtype="float32",
    published={"n_routed_experts": 32, "num_layers": 28,
               "vocab_size": 131072},
    # the traffic
    slots=4, clients=4, max_prompt=48, max_new=16, prompt_min=4,
    prompt_max=48, new_min=4, new_max=16, prefill_token_budget=16,
    requests=64, length_cycle=4, ramp_s=0.5, check_requests=6,
    limits={"token_logit_gap": 1e-3, "short_answers": 0},
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
    # 6 picks over 32 FFN + 16 identity outputs; 8 of the 32 held
    assert abs(eng["moe_ffn_picks_per_token"] - 4.0) < 0.5
    assert abs(eng["moe_held_pairs_per_token"] - 1.0) < 0.4
    assert 1.0 <= eng["moe_held_load_max_over_mean"] < 4.0
    assert eng["step_traces"] == 1 and eng["prefill_traces"] == 1


def test_traced_run_reports_the_counter_metrics():
    """Off the chip the trace holds no device program, so the three
    shares of a peak have nothing to read and are left out (never 0);
    the counter metrics and the engine's phases are there."""
    got = run(trace=True)["metrics"]
    assert {"moe_ffn_picks_per_token", "moe_held_load_max_over_mean",
            "longcat_serve_mfu", "engine_decode_step_ms",
            "engine_prefill_chunk_ms"} <= set(got), sorted(got)
    assert "serve_mfu" not in got and "serve_programs_roofline" not in got


def test_control_fp8_picks_other_tokens():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx = harness.Ctx(bench, harness.find(bench["workloads"], CELL,
                                          "workload"), 11, 1, False, SHRINK)
    ref = harness.load_module("reference", ctx.config_name)
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, 256, 64).astype(np.int32) for _ in range(3)]
    sound = ref.token_gaps(ctx.config, ctx.seed31, seqs, [32] * 3)
    assert max(ref.token_gaps(ctx.config, ctx.seed31, seqs, [32] * 3,
                              compute="float8_e4m3fn")) \
        > ctx.traffic["limits"]["token_logit_gap"]
    # the reference's own greedy continuation is not in these sequences:
    # random tokens lie far below the best logit
    assert min(sound) > 0.0


def test_control_bf16_router_moves_the_logits():
    """The router's own control: only its product in bfloat16. Picks
    flip, so logits move far past the 2e-4 by which the program's
    logits are held to the reference's (``tests/test_longcat.py``)."""
    import jax

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx = harness.Ctx(bench, harness.find(bench["workloads"], CELL,
                                          "workload"), 11, 1, False, SHRINK)
    ref = harness.load_module("reference", ctx.config_name)
    seq = [np.random.default_rng(5).integers(0, 256, 64).astype(np.int32)]
    with jax.default_matmul_precision("highest"):
        exact = np.asarray(ref.logits(ctx.config, ctx.seed31, seq)[0])
        low = np.asarray(ref.logits(ctx.config, ctx.seed31, seq,
                                    router_compute="bfloat16")[0])
    assert np.abs(low - exact).max() > 10 * 2e-4


def _no_experts(real):
    def layer(u, idx, gates, experts, *args, **kwargs):
        import jax
        import jax.numpy as jnp

        return real(u, idx, gates, jax.tree.map(jnp.zeros_like, experts),
                    *args, **kwargs)
    return layer


def _no_identity(real):
    def layer(*args, **kwargs):
        kwargs["identity"] = False
        return real(*args, **kwargs)
    return layer


def _gates_unscaled(real):
    return lambda u, w, b, k, scale: real(u, w, b, k, 1.0)


def _no_mla_scale(real):
    return lambda cfg, seed: dataclasses.replace(real(cfg, seed),
                                                 mla_scale_kv_lora=False)


def _no_rope(real):
    return lambda x, pos, theta: x


@pytest.mark.parametrize("name,wrap", [
    ("held_expert_layer", _no_experts),
    ("held_expert_layer", _no_identity),
    ("route_topk", _gates_unscaled),
    ("config_from_dict", _no_mla_scale),
    ("rope", _no_rope),
], ids=["expert_path_dropped", "identity_experts_skipped",
        "gates_not_scaled", "mla_scale_left_out",
        "rotary_slice_unrotated"])
def test_planted_fault_is_not_correct(monkeypatch, name, wrap):
    from multiverso_tpu.models import longcat

    monkeypatch.setattr(longcat, name, wrap(getattr(longcat, name)))
    line = run()
    assert not line["correct"] and "token_logit_gap" in failed(line), \
        line["compared"]
