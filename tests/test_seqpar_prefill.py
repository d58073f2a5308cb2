"""Sequence-parallel chunked prefill over the decode mesh.

The acceptance contract of the seqpar-prefill PR (docs/SERVING.md,
"Long-context prefill"):

* **seqpar == single-lane** — for a randomized mixed-length trace,
  every request served by a ``-prefill_sp`` engine returns
  token-for-token the sp-off engine's output, across {prefix cache
  on/off} x {tp 1, 2} and both attention backends (the chunk's
  sequence sharding, the ring/Ulysses collectives and the scatter back
  into the head-sharded paged pool are invisible in the tokens);
* **one compiled trace per program** — the fused step, the single-lane
  chunk AND the seqpar chunk each hold exactly ONE compiled trace
  after warmup, and ``decode_step_retraces`` stays 0: the partitioner
  runs at compile time, never per long prompt;
* **threshold routing** — prompts under ``-prefill_sp_threshold`` ride
  the existing single-lane chunk program bit-for-bit;
* **observability is gated** — seqpar engines (only) grow the stats
  keys, the ``decode.prefill_chunk`` span attrs and the flight
  recorder's ``sp_chunks`` column; sp-off engines are byte-identical
  to before;
* **ops parity in a cold process** — the ring/Ulysses kernels the
  serving path leans on match ``reference_attention`` under a 2-device
  virtual mesh pinned BEFORE jax imports (causal + non-causal, plus
  the ring pallas path's gradients), and the serving-shaped prefill
  entry points are bitwise the engine's chunk-attention math.

The suite's conftest forces 8 virtual CPU devices, so tp=2 runs
in-process everywhere below except the subprocess harness.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest


def _sp_cfg(**kw):
    from multiverso_tpu.models.transformer import TransformerConfig

    # n_heads divisible by tp=2 (ulysses head shards; megatron columns);
    # max_seq = max_prompt 24 + max_new 8 keeps T % tp == 0 for ring
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_seq=32)
    base.update(kw)
    return TransformerConfig(**base)


def _mixed_reqs(rng, n, vocab, max_prompt, max_new, threshold,
                shared_head=None):
    """Mixed-length (prompt, max_new) pairs: roughly half the prompts
    cross ``threshold`` (seqpar-routed), half stay under it
    (single-lane-routed), so one trace exercises BOTH programs; with
    ``shared_head`` half extend a fixed block-aligned prefix so the
    prefix cache actually hits."""
    reqs = []
    for i in range(n):
        head = shared_head if shared_head is not None and i % 2 == 0 \
            else np.empty(0, np.int32)
        lo, hi = ((threshold, max_prompt) if i % 2 == 0
                  else (1, threshold - 1))
        plen = int(rng.integers(max(1, lo - len(head)),
                                max(2, hi - len(head) + 1)))
        prompt = np.concatenate(
            [head, rng.integers(1, vocab, plen).astype(np.int32)])
        reqs.append((prompt, int(rng.integers(1, max_new + 1))))
    return reqs


def _serve(srv, model, reqs):
    futs = [srv.submit(model, {"prompt": p, "max_new": n})
            for p, n in reqs]
    return [f.result(timeout=120)["result"].tolist() for f in futs]


def _register(srv, name, lm, tp, sp, prefix=False, backend="ring",
              threshold=8, **kw):
    return srv.register_decoder(
        name, lm, slots=4, max_prompt=24, max_new=8, kv_block_size=4,
        prefill_token_budget=4, prefix_cache=prefix, decode_tp=tp,
        prefill_sp=sp, prefill_sp_backend=backend,
        prefill_sp_threshold=threshold, **kw)


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("prefix", [True, False])
def test_seqpar_matches_single_lane_oracle(mv_session, prefix, tp):
    """Randomized-trace oracle: a ``-prefill_sp`` engine's output
    tokens are identical to the sp-off engine's on the same mesh,
    prefix cache on and off, with every program tracing exactly once
    and the threshold routing both regimes through one trace."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _sp_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    rng = np.random.default_rng(5)
    head = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
    reqs = _mixed_reqs(rng, 12, cfg.vocab_size, max_prompt=24, max_new=8,
                       threshold=8, shared_head=head if prefix else None)

    outs, engines = {}, {}
    for sp in (False, True):
        engines[sp] = _register(srv, f"lm_sp{int(sp)}_tp{tp}", lm, tp, sp,
                                prefix=prefix)
        engines[sp].warmup()
        outs[sp] = _serve(srv, f"lm_sp{int(sp)}_tp{tp}", reqs)
    assert outs[True] == outs[False]

    for sp in (False, True):
        s = engines[sp].stats()
        assert s["step_traces"] == 1, s
        assert s["prefill_traces"] == 1, s
        assert s["decode_step_retraces"] == 0
        if prefix:
            assert s["prefix_hits"] > 0, \
                "trace never hit the prefix cache; test needs a new seed"
    sp_stats = engines[True].stats()
    assert sp_stats["seqpar_traces"] == 1, sp_stats
    assert sp_stats["seqpar_chunks"] > 0, \
        "no prompt was seqpar-routed; trace needs lengths >= threshold"
    assert sp_stats["prefill_sp"] == "ring"
    assert sp_stats["prefill_sp_chunk"] == 4 * tp
    # sp-off engines do not grow the surface
    assert "seqpar_traces" not in engines[False].stats()
    assert "prefill_sp" not in engines[False].stats()


def test_seqpar_ulysses_matches_single_lane(mv_session):
    """The all-to-all backend serves the same tokens as the sp-off
    engine on the tp=2 mesh — Q rows re-gather per head shard, the
    pool-native head sharding of K/V is used in place, and the reverse
    all_to_all restores the row sharding, all invisible in outputs."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _sp_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    rng = np.random.default_rng(9)
    reqs = _mixed_reqs(rng, 10, cfg.vocab_size, max_prompt=24, max_new=8,
                       threshold=8)
    outs, engines = {}, {}
    for sp in (False, True):
        engines[sp] = _register(srv, f"lm_uly{int(sp)}", lm, 2, sp,
                                backend="ulysses")
        engines[sp].warmup()
        outs[sp] = _serve(srv, f"lm_uly{int(sp)}", reqs)
    assert outs[True] == outs[False]
    s = engines[True].stats()
    assert s["prefill_sp"] == "ulysses"
    assert s["seqpar_traces"] == 1 and s["seqpar_chunks"] > 0
    assert s["decode_step_retraces"] == 0


def test_seqpar_validation(mv_session):
    """Fail-fast surface: seqpar refuses the int8 pool encoding, checks
    the backend name, and the ring backend's layout constraint (T
    divisible by tp) is caught at registration, not at the first long
    prompt."""
    from multiverso_tpu.log import FatalError
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    lm = TransformerLM(_sp_cfg())
    srv = InferenceServer("t")
    with pytest.raises(FatalError):     # int8 pools decode via their own
        srv.register_decoder("bad_quant", lm, max_prompt=24, max_new=8,
                             kv_block_size=4, prefill_token_budget=4,
                             kv_quant="int8", prefill_sp=True)
    with pytest.raises(FatalError):     # unknown backend
        srv.register_decoder("bad_backend", lm, max_prompt=24, max_new=8,
                             kv_block_size=4, prefill_token_budget=4,
                             prefill_sp=True, prefill_sp_backend="tree")
    with pytest.raises(FatalError):     # ring: T=23 not divisible by tp=2
        srv.register_decoder("bad_ring_t", lm, max_prompt=15, max_new=8,
                             kv_block_size=4, prefill_token_budget=4,
                             decode_tp=2, prefill_sp=True)
    with pytest.raises(FatalError):     # negative threshold
        srv.register_decoder("bad_thresh", lm, max_prompt=24, max_new=8,
                             kv_block_size=4, prefill_token_budget=4,
                             prefill_sp=True, prefill_sp_threshold=-1)


def test_seqpar_observability_spans_stats_recorder(mv_session):
    """The gated observability surface: on a seqpar engine every
    ``decode.prefill_chunk`` span says which program served it (``sp``
    0/1 + the backend), the flight recorder's ``sp_chunks`` column
    counts the iteration's seqpar chunks (and its meta names the
    backend), and an sp-off engine's spans/records carry none of it."""
    from multiverso_tpu import trace
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _sp_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    eng = _register(srv, "lm_sp", lm, 2, True)
    off = _register(srv, "lm_off", lm, 2, False)
    eng.warmup(), off.warmup()

    rng = np.random.default_rng(3)
    long_p = rng.integers(1, cfg.vocab_size, 20).astype(np.int32)
    short_p = rng.integers(1, cfg.vocab_size, 4).astype(np.int32)
    trace.enable(65536)
    trace.collector().clear()
    try:
        for model in ("lm_sp", "lm_off"):
            for p in (long_p, short_p):
                srv.submit(model, {"prompt": p,
                                   "max_new": 4}).result(timeout=120)
        deadline = time.monotonic() + 10.0
        while sum(s.name == "serve.request"
                  for s in trace.collector().spans()) < 4:
            assert time.monotonic() < deadline, "spans never arrived"
            time.sleep(0.005)
        spans = trace.collector().spans()
    finally:
        trace.disable()
        trace.collector().clear()

    def chunks_of(model):
        roots = {s.trace_id for s in spans
                 if s.name == "serve.request" and s.attrs["model"] == model}
        return [s for s in spans if s.name == "decode.prefill_chunk"
                and s.trace_id in roots]

    sp_chunks = chunks_of("lm_sp")
    assert sp_chunks and all(
        {"sp", "sp_backend"} <= set(s.attrs) for s in sp_chunks)
    assert {s.attrs["sp"] for s in sp_chunks} == {0, 1}   # both regimes
    assert all(s.attrs["sp_backend"] == "ring" for s in sp_chunks)
    # the seqpar chunk is budget*tp wide, the single-lane chunk budget
    assert {s.attrs["budget"] for s in sp_chunks
            if s.attrs["sp"]} == {8}
    assert {s.attrs["budget"] for s in sp_chunks
            if not s.attrs["sp"]} == {4}
    off_chunks = chunks_of("lm_off")
    assert off_chunks and all("sp" not in s.attrs for s in off_chunks)

    assert eng.recorder.meta["prefill_sp"] == "ring"
    assert "prefill_sp" not in off.recorder.meta
    recs = eng.recorder.records()
    assert sum(r["sp_chunks"] for r in recs if r["sp_chunks"] > 0) \
        == eng.stats()["seqpar_chunks"] > 0
    assert all(r["sp_chunks"] == -1 for r in off.recorder.records())


def test_full_hit_admission_not_serialized(mv_session):
    """Prefix-cache full hits cost zero prefill chunks, so they must
    not consume the chunked loop's one-admission-per-iteration slot: a
    burst of cache-hit prompts co-admits with an equivalent short
    prompt in the SAME engine iteration (whose first chunk also runs),
    instead of trickling in at one request per iteration."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _sp_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    eng = srv.register_decoder("lm", lm, slots=6, max_prompt=24, max_new=8,
                               kv_block_size=4, prefill_token_budget=4,
                               prefix_cache=True)
    eng.warmup()
    rng = np.random.default_rng(11)
    doc = rng.integers(1, cfg.vocab_size, 12).astype(np.int32)  # 3 blocks
    fresh = rng.integers(1, cfg.vocab_size, 4).astype(np.int32)
    # register the prefix: after this completes, `doc` is a FULL hit
    srv.submit("lm", {"prompt": doc, "max_new": 4}).result(timeout=120)

    for _ in range(3):          # scheduling-tolerant: retry the burst
        # a long generation keeps the loop mid-iteration while the
        # burst lands in the queue together
        blocker = srv.submit("lm", {"prompt": fresh, "max_new": 8})
        time.sleep(0.02)
        futs = [srv.submit("lm", {"prompt": doc, "max_new": 2})
                for _ in range(3)]
        # an UNCACHED short rides the same burst: its first (and only)
        # chunk must run in the iteration that admitted it
        futs.append(srv.submit(
            "lm", {"prompt": rng.integers(1, cfg.vocab_size,
                                          4).astype(np.int32),
                   "max_new": 2}))
        for f in futs + [blocker]:
            f.result(timeout=120)
        recs = eng.recorder.records()
        co_admitted = [r for r in recs if len(r["admitted"]) >= 2]
        if co_admitted:
            break
    assert co_admitted, \
        "full-hit admissions serialized to one request per iteration"
    # ...and at least one co-admission also ran a prefill chunk in the
    # same iteration: the zero-cost hit did not displace real work
    assert any(r["prefill_toks"] > 0 for r in co_admitted)
    assert eng.stats()["prefix_hits"] > 0


def test_seqpar_ops_parity_subprocess_2dev():
    """Cold-process ops parity: XLA_FLAGS pins a 2-device virtual CPU
    mesh BEFORE jax imports (the tools/scaling_bench.py pattern), then
    the kernels the serving path leans on are checked against
    ``reference_attention`` — ring + Ulysses, causal and non-causal,
    the ring pallas path's gradients — and the serving-shaped prefill
    entry points return BITWISE the engine's chunk-attention math."""
    script = """
import numpy as np
import jax
import jax.numpy as jnp
assert jax.device_count() == 2, jax.device_count()
from multiverso_tpu.ops import (reference_attention, ring_attention,
                                ring_prefill_attention, ulysses_attention,
                                ulysses_prefill_attention)
from multiverso_tpu.ops.ring_attention import _prefix_chunk_attn
from multiverso_tpu.topology import SEQ_AXIS, make_mesh

mesh = make_mesh((2,), axis_names=(SEQ_AXIS,))
rng = np.random.default_rng(0)
mk = lambda: jnp.asarray(rng.standard_normal((8, 2, 8)), jnp.float32)
q, k, v = mk(), mk(), mk()
for causal in (False, True):
    ref = np.asarray(reference_attention(q, k, v, causal=causal))
    np.testing.assert_allclose(
        np.asarray(ring_attention(q, k, v, mesh, causal=causal)),
        ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ulysses_attention(q, k, v, mesh, causal=causal)),
        ref, rtol=1e-4, atol=1e-5)

# ring pallas path (interpret mode on CPU): grads vs the reference
gp = jax.grad(lambda q, k, v: jnp.sum(ring_attention(
    q, k, v, mesh, causal=True, impl="pallas") ** 2),
    argnums=(0, 1, 2))(q, k, v)
gr = jax.grad(lambda q, k, v: jnp.sum(reference_attention(
    q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
for a, b in zip(gp, gr):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-3, atol=1e-4)

# serving-shaped entry points: bitwise the engine's chunk math
C, T, H, D = 8, 16, 2, 16
dh = D // H
qc = jnp.asarray(rng.standard_normal((C, D)), jnp.float32)
kc = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
vc = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
rows = 4 + jnp.arange(C)
ref2 = np.asarray(_prefix_chunk_attn(
    qc.reshape(C, H, dh), kc.reshape(T, H, dh), vc.reshape(T, H, dh),
    rows, dh)).reshape(C, D)
np.testing.assert_array_equal(np.asarray(ring_prefill_attention(
    qc, kc, vc, H, jnp.int32(4), mesh)), ref2)
np.testing.assert_array_equal(np.asarray(ulysses_prefill_attention(
    qc, kc, vc, H, jnp.int32(4), mesh)), ref2)
print("SEQPAR_OPS_OK")
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, cwd=repo,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SEQPAR_OPS_OK" in proc.stdout, proc.stdout + proc.stderr
