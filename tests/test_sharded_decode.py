"""Sharded decode: tensor-parallel decode mesh correctness.

The acceptance contract of the sharded-decode PR (docs/SERVING.md,
"Sharded decode"):

* **sharded == replicated** — for a randomized admission trace, every
  request served by a ``decode_tp=2`` engine returns token-for-token
  the ``decode_tp=1`` replicated engine's output, with prefix caching
  enabled AND disabled (head sharding, the Megatron all-reduces, and
  the head-sharded K/V pools are invisible in the tokens);
* **one compiled trace per program, per mesh** — the fused step /
  chunk / CoW programs each hold exactly ONE compiled trace after
  warmup under the decode mesh, and ``decode_step_retraces`` stays 0:
  the spmd partitioner runs at compile time, never in the hot loop
  (the PR 2 ~10x drag, asserted gone);
* **mesh-aware introspection** — ``stats()`` reports ``decode_tp``/
  ``mesh_devices``/per-device KV bytes, the flight recorder's summary
  carries the mesh config;
* **cold-process wiring** — a subprocess that pins a 2-device virtual
  CPU mesh via ``XLA_FLAGS`` BEFORE importing jax (the
  ``tools/scaling_bench.py`` pattern) serves tp=2 end to end.

The suite's conftest forces 8 virtual CPU devices, so tp=2 runs
in-process everywhere below except the subprocess smoke.
"""

import os
import subprocess
import sys

import numpy as np
import pytest


def _tp_cfg(**kw):
    from multiverso_tpu.models.transformer import TransformerConfig

    # n_heads and d_ff divisible by tp=2; d_model/vocab divisible by the
    # 8-way train mesh (TransformerLM shards embed rows / ffn columns)
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_seq=48)
    base.update(kw)
    return TransformerConfig(**base)


def _random_reqs(rng, n, vocab, max_prompt, max_new, shared_head=None):
    """(prompt, max_new) pairs; with ``shared_head`` half the prompts
    extend a fixed block-aligned prefix so the prefix cache actually
    hits."""
    reqs = []
    for i in range(n):
        plen = int(rng.integers(1, max_prompt - (len(shared_head)
                                                 if shared_head is not None
                                                 else 0) + 1))
        tail = rng.integers(1, vocab, plen).astype(np.int32)
        prompt = (np.concatenate([shared_head, tail])
                  if shared_head is not None and i % 2 == 0 else tail)
        reqs.append((prompt, int(rng.integers(1, max_new + 1))))
    return reqs


def _serve(srv, model, reqs):
    futs = [srv.submit(model, {"prompt": p, "max_new": n})
            for p, n in reqs]
    return [f.result(timeout=120)["result"].tolist() for f in futs]


@pytest.mark.parametrize("budget", [5, 16])
@pytest.mark.parametrize("prefix", [True, False])
def test_sharded_matches_replicated_oracle(mv_session, prefix, budget):
    """Randomized-trace oracle: tp=2 output tokens are identical to the
    tp=1 replicated path's, prefix cache on and off — and when it is
    on, the trace actually exercises cache hits. Budget 5 splits a
    prompt into up to four chunks; 16 = ``max_prompt`` holds every
    prompt in one."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _tp_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    rng = np.random.default_rng(3)
    head = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
    reqs = _random_reqs(rng, 12, cfg.vocab_size, max_prompt=14,
                        max_new=8, shared_head=head if prefix else None)

    outs, engines = {}, {}
    for tp in (1, 2):
        engines[tp] = srv.register_decoder(
            f"lm_tp{tp}", lm, slots=4, max_prompt=16, max_new=8,
            kv_block_size=4, prefill_token_budget=budget,
            prefix_cache=prefix, decode_tp=tp)
        engines[tp].warmup()
        outs[tp] = _serve(srv, f"lm_tp{tp}", reqs)
    assert outs[2] == outs[1]
    for tp in (1, 2):
        s = engines[tp].stats()
        assert s["step_traces"] == 1, s
        assert s["prefill_traces"] == 1, s
        assert s["decode_step_retraces"] == 0
        if prefix:
            assert s["prefix_hits"] > 0, \
                "trace never hit the prefix cache; test needs a new seed"


def test_sharded_spec_decode_matches_replicated(mv_session):
    """Speculative decoding under the decode mesh: a tp=2 spec_k=3
    engine is token-identical to the tp=1 spec engine AND the plain
    tp=1 baseline on a repetitive trace, with one compiled verify
    trace per mesh, zero step retraces, and real acceptance (the
    sharded verify program is exercised, not just compiled)."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _tp_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    rng = np.random.default_rng(11)
    reqs = []
    for _ in range(8):
        motif = rng.integers(1, cfg.vocab_size,
                             int(rng.integers(2, 5))).astype(np.int32)
        plen = int(rng.integers(4, 13))
        prompt = np.tile(motif, -(-plen // len(motif)))[:plen]
        reqs.append((prompt.astype(np.int32), int(rng.integers(4, 9))))

    outs, engines = {}, {}
    for label, tp, k in (("sp_tp2", 2, 3), ("sp_tp1", 1, 3),
                         ("plain_tp1", 1, 0)):
        engines[label] = srv.register_decoder(
            f"lm_{label}", lm, slots=4, max_prompt=12, max_new=8,
            kv_block_size=4, prefill_token_budget=5, decode_tp=tp,
            spec_k=k)
        engines[label].warmup()
        outs[label] = _serve(srv, f"lm_{label}", reqs)
    assert outs["sp_tp2"] == outs["sp_tp1"] == outs["plain_tp1"]
    for label in ("sp_tp2", "sp_tp1"):
        s = engines[label].stats()
        assert s["verify_traces"] == 1, s
        assert s["step_traces"] == 1
        assert s["decode_step_retraces"] == 0
        assert s["spec_accepted"] > 0, \
            f"{label} never accepted a draft; test needs a new seed"


def test_sharded_stats_and_recorder_are_mesh_aware(mv_session):
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer
    from multiverso_tpu.serving.block_pool import kv_bytes_per_block

    cfg = _tp_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    engine = srv.register_decoder(
        "lm_sh", lm, slots=4, max_prompt=8, max_new=8, kv_block_size=4,
        decode_tp=2)
    engine.warmup()
    srv.submit("lm_sh", np.array([3, 5], np.int32)).result(timeout=120)
    s = engine.stats()
    assert s["decode_tp"] == 2
    assert s["mesh_devices"] == 2
    total_kv = (s["kv_pool_blocks"] + 1) * kv_bytes_per_block(
        cfg.n_layers, cfg.d_model, 4)
    assert s["kv_bytes_per_device"] == total_kv // 2
    assert s["decode_step_retraces"] == 0
    assert s["pin_copies"] == 1
    if engine.recorder is not None:
        summ = engine.recorder.summary()
        assert summ["decode_tp"] == 2 and summ["mesh_devices"] == 2


def test_decode_tp_validation(mv_session):
    """Fail-fast surface: tp must divide n_heads/d_ff and cannot exceed
    the visible device count."""
    from multiverso_tpu.log import FatalError
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    lm = TransformerLM(_tp_cfg())
    srv = InferenceServer("t")
    with pytest.raises(FatalError):        # 3 does not divide n_heads=4
        srv.register_decoder("bad_heads", lm, kv_block_size=4,
                             decode_tp=3)
    with pytest.raises(FatalError):        # more than the 8 test devices
        srv.register_decoder("bad_ndev", lm, kv_block_size=4,
                             decode_tp=100)


def test_sharded_subprocess_smoke():
    """Cold-process wiring: XLA_FLAGS pins a 2-device virtual CPU mesh
    BEFORE jax imports (the tools/scaling_bench.py:48 pattern), and a
    decode_tp=2 engine serves token-identically to tp=1 in that
    process."""
    script = """
import numpy as np
import multiverso_tpu as mv
mv.init(["t", "-log_level=error"])
import jax
assert jax.device_count() == 2, jax.device_count()
from multiverso_tpu.models.transformer import TransformerConfig, TransformerLM
from multiverso_tpu.serving import InferenceServer
cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=2, n_layers=1,
                        d_ff=32, max_seq=16)
lm = TransformerLM(cfg)
srv = InferenceServer("sub")
outs = {}
for tp in (1, 2):
    e = srv.register_decoder(f"lm{tp}", lm, slots=2, max_prompt=6,
                             max_new=6, kv_block_size=2, decode_tp=tp,
                             watchdog=False)
    e.warmup()
    f = srv.submit(f"lm{tp}", np.array([3, 5, 7], np.int32))
    outs[tp] = f.result(timeout=120)["result"].tolist()
    assert e.stats()["decode_step_retraces"] == 0
assert outs[1] == outs[2], outs
mv.shutdown()
print("SHARDED_OK", outs[2])
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, cwd=repo,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SHARDED_OK" in proc.stdout, proc.stdout + proc.stderr
