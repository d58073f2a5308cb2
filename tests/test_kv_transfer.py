"""Disaggregated prefill/decode: the KV-block transfer plane.

The acceptance contract of the disaggregation PR (docs/SERVING.md,
"Disaggregated prefill/decode"):

* **splice-at-arrival is bit-exact** — a prompt prefilled on one engine,
  shipped as a :mod:`kv_transfer` payload, and spliced into another
  engine's pool decodes token-for-token identically to a unified engine
  (and the per-request ``greedy_decode`` oracle), with the compiled
  trace set unchanged: 1 step + 1 chunk + 1 CoW + (1 fetch + 1 splice);
* **dedup never re-ships a warm prefix** — source-side (advertised
  ``known`` hashes ride as metadata, zero bytes) and arrival-side (a
  block already content-addressed is skipped at splice time);
* **loss degrades to latency, never tokens** — a chaos-dropped payload,
  a stale snapshot version, or a killed prefill replica all fall back
  to local re-prefill / unified admission with ``requests_lost == 0``
  and bit-identical output.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _small_cfg(**kw):
    from multiverso_tpu.models.transformer import TransformerConfig

    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                d_ff=64, max_seq=48)
    base.update(kw)
    return TransformerConfig(**base)


def _oracle(cfg, params, prompt, max_new):
    import jax.numpy as jnp

    from multiverso_tpu.models.transformer import greedy_decode

    return np.asarray(greedy_decode(
        cfg, params, jnp.asarray(prompt[None]),
        jnp.asarray([len(prompt)]), max_new, None))[0]


# -- wire format --------------------------------------------------------------

def test_payload_roundtrip_and_accounting():
    """Pure wire-format unit test: pack/unpack round-trips bytes, the
    byte accounting counts shipped blocks only, and drop_blocks keeps
    the metadata that makes the loss observable."""
    from multiverso_tpu.serving import kv_transfer as kt

    shape, dtype = (2, 4, 8), "float32"
    rng = np.random.default_rng(0)
    payload = kt.new_payload(prompt_len=9, block_size=4,
                             snapshot_version=3, shape=shape, dtype=dtype)
    assert kt.validate(payload) is None
    k0, v0 = (rng.normal(size=shape).astype(np.float32) for _ in range(2))
    kt.add_block(payload, "aa" * 16, k0, v0)
    kt.add_block(payload, "bb" * 16)          # source dedup: hash only
    assert payload["hashes"] == ["aa" * 16, "bb" * 16]
    assert payload["dedup_blocks"] == 1
    assert kt.shipped_hashes(payload) == {"aa" * 16}
    per = kt.block_nbytes(shape, dtype)
    assert per == 2 * 2 * 4 * 8 * 4
    assert kt.payload_bytes(payload) == per
    k1, v1 = kt.unpack_block(payload["blocks"]["aa" * 16], shape, dtype)
    np.testing.assert_array_equal(k0, k1)
    np.testing.assert_array_equal(v0, v1)
    with pytest.raises(ValueError):           # truncated record fails loudly
        kt.unpack_block(payload["blocks"]["aa" * 16], (2, 4, 9), dtype)
    dropped = kt.drop_blocks(payload)
    assert dropped["dropped"] and not dropped["blocks"]
    assert dropped["hashes"] == payload["hashes"]     # loss is observable
    assert kt.payload_bytes(dropped) == 0
    assert payload["blocks"], "drop_blocks must not mutate the original"
    # malformed payloads: reason strings, never exceptions
    assert kt.validate("nope") is not None
    assert kt.validate({"v": 99}) is not None
    assert kt.validate(dict(payload, shape=[1, 2])) is not None
    stray = dict(payload, hashes=[])
    assert kt.validate(stray) is not None     # shipped block off-chain


# -- splice-at-arrival oracle -------------------------------------------------

@pytest.mark.parametrize("oracle_prefix", [True, False],
                         ids=["oracle-cache-on", "oracle-cache-off"])
def test_disagg_splice_bit_exact_vs_unified(mv_session, oracle_prefix):
    """The tentpole oracle: prefill on engine A, ship the payload,
    splice into engine B, submit the same prompt — B's tokens equal the
    unified engine's (cache on AND off) and the greedy_decode oracle,
    while the transfer actually happened (full blocks crossed, the
    admission full-hit the spliced prefix) and no program retraced."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer
    from multiverso_tpu.serving.workloads import _jit_cache_size

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    kw = dict(slots=2, max_prompt=16, max_new=8, kv_block_size=4,
              prefill_token_budget=4, watchdog=False)
    pf = srv.register_decoder("pf", lm, prefix_cache=True, **kw)
    dec = srv.register_decoder("dec", lm, prefix_cache=True, **kw)
    uni = srv.register_decoder("uni", lm, prefix_cache=oracle_prefix, **kw)
    for e in (pf, dec, uni):
        e.warmup()
    assert pf.supports_transfer and dec.supports_transfer
    params, _ = lm.snapshot_params()

    rng = np.random.default_rng(7)
    prompts = [
        rng.integers(1, cfg.vocab_size, 8).astype(np.int32),   # 2 full blocks
        rng.integers(1, cfg.vocab_size, 10).astype(np.int32),  # 2 full + tail
        rng.integers(1, cfg.vocab_size, 3).astype(np.int32),   # no full block
    ]
    for i, p in enumerate(prompts):
        reply = pf.submit_prefill(p).result(timeout=120)
        payload = reply["xfer"]
        n_full = len(p) // 4
        assert len(payload["hashes"]) == n_full
        assert len(payload["blocks"]) == n_full   # nothing advertised yet
        info = dec.splice(payload)
        assert "skipped" not in info, info
        assert info["xfer_blocks"] == n_full
        out = dec.submit(p, 6, xfer_info=info).result(
            timeout=120)["result"]
        want = _oracle(cfg, params, p, 6)
        np.testing.assert_array_equal(out, want, err_msg=f"prompt {i}")
        np.testing.assert_array_equal(
            uni.submit(p, 6).result(timeout=120)["result"], want,
            err_msg=f"unified prompt {i}")

    pfs, decs = pf.stats(), dec.stats()
    assert pfs["xfer_blocks"] == decs["xfer_blocks"] == 4
    assert pfs["kv_bytes_moved"] == decs["kv_bytes_moved"] > 0
    # the 2-full-block prompt full-hit its spliced prefix: decode went
    # live at P-1 through the PR 8 CoW path, saving its whole prefill
    assert decs["prefill_tokens_saved"] >= 8
    assert decs["cow_copies"] >= 1
    # one-trace invariant, transfer plane included: 1 step + 1 chunk per
    # engine, and exactly (1 fetch + 1 splice) compiled across all the
    # transfers (block ids are data, not shapes)
    for e in (pf, dec, uni):
        assert e.step_cache_size() == 1
        assert e.prefill_cache_size() == 1
        assert e.stats()["decode_step_retraces"] == 0
    assert pf.transfer_cache_size() == 2
    assert dec.transfer_cache_size() == 2
    assert _jit_cache_size(dec._cow_fn) == 1
    for e in (pf, dec):
        e._pool.check()
        assert e.pool_drift() is None


def test_disagg_dedup_source_and_arrival(mv_session):
    """Dedup both ways: ``known`` hashes make the source ship metadata
    only (zero bytes), and an unadvertised re-ship dedups at arrival
    (the pool's content index catches it). Either way the follow-up
    admission stays bit-exact."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    kw = dict(slots=2, max_prompt=16, max_new=8, kv_block_size=4,
              prefill_token_budget=4, prefix_cache=True, watchdog=False)
    pf = srv.register_decoder("pf", lm, **kw)
    dec = srv.register_decoder("dec", lm, **kw)
    for e in (pf, dec):
        e.warmup()
    params, _ = lm.snapshot_params()
    rng = np.random.default_rng(11)
    p = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
    want = _oracle(cfg, params, p, 6)

    first = pf.submit_prefill(p).result(timeout=120)["xfer"]
    assert len(first["blocks"]) == 2
    info = dec.splice(first)
    assert info["xfer_blocks"] == 2 and info["dedup_blocks"] == 0
    np.testing.assert_array_equal(
        dec.submit(p, 6, xfer_info=info).result(timeout=120)["result"],
        want)

    # source-side: the receiver advertised the chain -> zero bytes move
    from multiverso_tpu.serving import kv_transfer as kt

    known = [h.hex() for h in dec._pool.indexed_hashes()]
    second = pf.submit_prefill(p, known_hashes=known).result(
        timeout=120)["xfer"]
    assert second["dedup_blocks"] == 2 and not second["blocks"]
    assert kt.payload_bytes(second) == 0
    info2 = dec.splice(second)
    assert info2["xfer_blocks"] == 0 and info2["dedup_blocks"] == 2
    np.testing.assert_array_equal(
        dec.submit(p, 6, xfer_info=info2).result(timeout=120)["result"],
        want)

    # arrival-side: an unadvertised repeat ships bytes, splices none
    third = pf.submit_prefill(p).result(timeout=120)["xfer"]
    assert len(third["blocks"]) == 2      # the source did not know
    info3 = dec.splice(third)
    assert info3["xfer_blocks"] == 0 and info3["dedup_blocks"] == 2
    s = dec.stats()
    assert s["xfer_dedup_blocks"] == 4
    assert 0.0 < s["xfer_dedup_hit_rate"] <= 1.0
    # the prefill engine's side of the ledger: one advertised chain
    assert pf.stats()["xfer_dedup_blocks"] == 2
    dec._pool.check()
    assert dec.pool_drift() is None


def test_splice_rejects_bad_payloads_and_chain_gaps(mv_session):
    """The degradation ladder: stale version / wrong geometry skip
    whole; a chain gap (chaos-dropped or missing record) splices the
    good prefix and STOPS; none of it ever breaks the follow-up
    admission, which just re-prefills what the splice did not provide."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer
    from multiverso_tpu.serving import kv_transfer as kt

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    kw = dict(slots=2, max_prompt=16, max_new=8, kv_block_size=4,
              prefill_token_budget=4, prefix_cache=True, watchdog=False)
    pf = srv.register_decoder("pf", lm, **kw)
    dec = srv.register_decoder("dec", lm, **kw)
    for e in (pf, dec):
        e.warmup()
    params, _ = lm.snapshot_params()
    rng = np.random.default_rng(13)
    p = rng.integers(1, cfg.vocab_size, 12).astype(np.int32)  # 3 blocks
    want = _oracle(cfg, params, p, 6)
    payload = pf.submit_prefill(p).result(timeout=120)["xfer"]

    bad_version = dict(payload, snapshot_version=999)
    info = dec.splice(bad_version)
    assert info["xfer_blocks"] == 0 and "skipped" in info
    bad_bs = dict(payload, block_size=8)
    assert "skipped" in dec.splice(bad_bs)
    assert "skipped" in dec.splice({"v": 99})
    # chaos drop: header + hashes survive, zero blocks splice
    info = dec.splice(kt.drop_blocks(payload))
    assert info["xfer_blocks"] == 0 and info["dedup_blocks"] == 0
    # a gap mid-chain: blocks AFTER the gap never splice (chain hashes
    # only mean anything as prefixes)
    gap = dict(payload, blocks={h: r for h, r in payload["blocks"].items()
                                if h != payload["hashes"][1]})
    info = dec.splice(gap)
    assert info["xfer_blocks"] == 1
    # after all that abuse the prompt still decodes bit-exactly
    np.testing.assert_array_equal(
        dec.submit(p, 6).result(timeout=120)["result"], want)
    dec._pool.check()
    assert dec.pool_drift() is None


def test_transfer_unsupported_surfaces(mv_session):
    """Engines without the prefix-cache gate refuse prefill-only
    admissions loudly and splice as a zero-accounting no-op (the
    replica path feeds payloads to whatever engine it has)."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    plain = srv.register_decoder("plain", lm, slots=2, max_prompt=8,
                                 max_new=8, kv_block_size=4,
                                 prefill_token_budget=4,
                                 prefix_cache=False, watchdog=False)
    plain.warmup()
    assert not plain.supports_transfer
    with pytest.raises(RuntimeError):
        plain.submit_prefill(np.arange(1, 9, dtype=np.int32))
    info = plain.splice({"v": 1})
    assert info["xfer_blocks"] == 0 and info["skipped"] == "unsupported"
    assert plain.transfer_cache_size() == 0


def test_disagg_decode_tp2_subprocess():
    """Cross-mesh transfer: a tp=1 prefill engine's payload splices
    into a decode_tp=2 engine and decodes token-identically to the
    tp=2 unified engine — the wire format carries logical (L, Bs, D)
    blocks, so the receiver's sharding is its own business."""
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
kw = dict(slots=2, max_prompt=8, max_new=6, kv_block_size=2,
          prefill_token_budget=2, prefix_cache=True, watchdog=False)
pf = srv.register_decoder("pf", lm, decode_tp=1, **kw)
outs = {}
for tp in (1, 2):
    dec = srv.register_decoder(f"dec{tp}", lm, decode_tp=tp, **kw)
    uni = srv.register_decoder(f"uni{tp}", lm, decode_tp=tp, **kw)
    for e in (dec, uni):
        e.warmup()
    p = np.array([3, 5, 7, 2, 9, 4], np.int32)       # 3 full blocks
    payload = pf.submit_prefill(p).result(timeout=120)["xfer"]
    info = dec.splice(payload)
    assert info.get("xfer_blocks") == 3, info
    out = dec.submit(p, 5, xfer_info=info).result(timeout=120)["result"]
    ref = uni.submit(p, 5).result(timeout=120)["result"]
    assert out.tolist() == ref.tolist(), (tp, out, ref)
    assert dec.stats()["prefill_tokens_saved"] >= 6
    assert dec.stats()["decode_step_retraces"] == 0
    outs[tp] = out.tolist()
assert outs[1] == outs[2], outs
mv.shutdown()
print("DISAGG_TP_OK", outs[2])
"""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    proc = subprocess.run(
        [sys.executable, "-c", script], env=env, cwd=_REPO,
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "DISAGG_TP_OK" in proc.stdout, proc.stdout + proc.stderr


# -- the two-stage fleet ------------------------------------------------------

class _KV:
    """The three client calls the wire uses, over a local dict."""

    def __init__(self):
        self._d = {}
        self._cv = threading.Condition()

    def key_value_set(self, key, val, allow_overwrite=False):
        with self._cv:
            self._d[key] = val
            self._cv.notify_all()

    def blocking_key_value_get(self, key, timeout_ms):
        deadline = time.monotonic() + timeout_ms / 1000.0
        with self._cv:
            while key not in self._d:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(f"NOT_FOUND: {key}")
                self._cv.wait(left)
            return self._d[key]

    def key_value_try_get(self, key):
        with self._cv:
            if key not in self._d:
                raise KeyError(f"NOT_FOUND: {key}")
            return self._d[key]


def _mk_disagg_fleet(label, lm, roles=("prefill", "decode"), hb_ms=60,
                     chaos=None, dead_after_s=None, **engine_kw):
    from multiverso_tpu.serving import (FleetConfig, FleetRouter,
                                        ReplicaServer)
    from multiverso_tpu.serving.decode_engine import (DecodeEngine,
                                                      DecodeEngineConfig)

    kw = dict(slots=2, max_prompt=16, max_new=8, kv_block_size=4,
              prefill_token_budget=4, prefix_cache=True, watchdog=False)
    kw.update(engine_kw)
    engines = []
    for r, _ in enumerate(roles):
        engine = DecodeEngine(f"{label}{r}", lm, DecodeEngineConfig(**kw))
        engine.warmup()
        engines.append(engine)
    kv = _KV()
    size = len(roles) + 1
    router = FleetRouter(size, kv, label=label, name=label,
                         fleet_config=FleetConfig(heartbeat_ms=hb_ms,
                                                  dead_after_s=dead_after_s,
                                                  deadline_s=120.0))
    replicas = [ReplicaServer(r + 1, size, kv, engines[r], label=label,
                              heartbeat_ms=hb_ms, role=role)
                for r, role in enumerate(roles)]
    if chaos is not None:
        from multiverso_tpu.serving import FaultPlan

        replicas[0].chaos = FaultPlan(chaos, kill_fn=replicas[0].die)
    # wait for UP **and** for the roles to ride the heartbeats: the
    # two-stage path only engages once the router knows who is who
    deadline = time.monotonic() + 20
    while True:
        rows = router.replica_rows()
        if (router.stats()["up"] == len(roles)
                and [row["role"] for row in rows] == list(roles)):
            break
        assert time.monotonic() < deadline, rows
        time.sleep(0.01)
    return kv, router, replicas, engines


def _stop_disagg(router, replicas, engines):
    router.stop()
    for rep in replicas:
        try:
            rep.stop(stop_engine=False)
        except Exception:
            pass
    for engine in engines:
        engine.stop()


def test_fleet_two_stage_dispatch_end_to_end(mv_session):
    """1 prefill + 1 decode replica behind the router: requests flow
    stage-1 -> MSG_XFER -> stage-2, outputs are oracle-exact, the
    transfer ledger moves, and a repeated prompt's second transfer
    moves ~zero bytes (the router's shipped book + the decode side's
    heartbeat advertisement)."""
    from multiverso_tpu import trace
    from multiverso_tpu.models.transformer import TransformerLM

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    kv, router, replicas, engines = _mk_disagg_fleet("disagg", lm)
    params, _ = lm.snapshot_params()
    rng = np.random.default_rng(17)
    prompts = [rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(3)]
    prompts += [rng.integers(1, cfg.vocab_size, 3).astype(np.int32)]
    trace.enable(65536)
    try:
        futs = [router.submit(p, 6) for p in prompts]
        outs = [f.result(timeout=120) for f in futs]
        for p, out in zip(prompts, outs):
            np.testing.assert_array_equal(
                out["result"], _oracle(cfg, params, p, 6))
            assert out["replica"] == 2        # tokens come from decode
        st = router.stats()
        assert st["requests_lost"] == 0
        assert st["output_mismatches"] == 0
        assert st["kv_xfers"] == len(prompts)
        assert st["xfer_blocks"] == 6         # 3 x 2 full blocks; the
        # short prompt has no full block and ships metadata only
        assert st["kv_bytes_moved"] > 0
        moved_before = st["kv_bytes_moved"]
        # repeat an already-shipped prompt: the chain is in the shipped
        # book, so the second transfer is metadata-only
        out = router.predict(prompts[0], 6)
        np.testing.assert_array_equal(
            out["result"], _oracle(cfg, params, prompts[0], 6))
        st = router.stats()
        assert st["kv_bytes_moved"] == moved_before, "repeat re-shipped"
        assert st["xfer_dedup_blocks"] >= 2
        assert st["xfer_dedup_hit_rate"] > 0.0
        assert replicas[0].xfers_sent == len(prompts) + 1
        assert replicas[1].xfers_spliced == len(prompts) + 1
        assert replicas[0].stats()["role"] == "prefill"
        rows = router.replica_rows()
        assert [r["role"] for r in rows] == ["prefill", "decode"]
        spans = trace.collector().spans()
    finally:
        trace.disable()
        trace.collector().clear()
        _stop_disagg(router, replicas, engines)
    xfers = [sp for sp in spans if sp.name == "kv.transfer"]
    assert len(xfers) == len(prompts) + 1
    for sp in xfers:
        assert "xfer_blocks" in sp.attrs and "xfer_bytes" in sp.attrs
        assert "dedup_blocks" in sp.attrs
    # the prefill engine's ledger agrees with the router's
    pfs = engines[0].stats()
    assert pfs["xfer_blocks"] == 6
    assert pfs["xfer_dedup_blocks"] >= 2


def test_fleet_chaos_xfer_drop_degrades_not_breaks(mv_session):
    """``kv_xfer_drop=1`` strips the first payload's K/V bytes on the
    wire: the decode side splices nothing, re-prefills locally, and
    every output stays bit-identical with requests_lost == 0."""
    from multiverso_tpu.models.transformer import TransformerLM

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    kv, router, replicas, engines = _mk_disagg_fleet(
        "xdrop", lm, chaos="kv_xfer_drop=1")
    params, _ = lm.snapshot_params()
    rng = np.random.default_rng(19)
    prompts = [rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(3)]
    try:
        futs = [router.submit(p, 6) for p in prompts]
        for p, f in zip(prompts, futs):
            np.testing.assert_array_equal(
                f.result(timeout=120)["result"],
                _oracle(cfg, params, p, 6))
        st = router.stats()
        assert st["requests_lost"] == 0
        assert st["output_mismatches"] == 0
        assert replicas[0].chaos.counts["kv_xfer_drops"] == 1
        # the dropped transfer moved strictly fewer blocks than a clean
        # 3x2-block run — the loss is visible in the ledger
        assert st["xfer_blocks"] < 6
    finally:
        _stop_disagg(router, replicas, engines)


def test_fleet_prefill_kill_falls_back_to_unified(mv_session):
    """Killing the only prefill replica mid-trace forces the router's
    unified fallback: stage-1 in-flights re-dispatch to the decode
    replica as plain requests, everything completes bit-identically,
    and requests_lost stays 0."""
    from multiverso_tpu.models.transformer import TransformerLM

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    # a DEAD verdict after 50 silent heartbeat periods, not the default
    # 2: this test counts deaths, and on a CPU it shares with five other
    # workers a heartbeat 120 ms late is a starved thread, not a death
    # (the survivor flapped DEAD -> PROBING -> UP and read 5 deaths)
    hb_ms = 60
    dead_after_s = 50 * hb_ms / 1000.0
    kv, router, replicas, engines = _mk_disagg_fleet(
        "pfkill", lm, hb_ms=hb_ms, chaos="kill_at_request=2",
        dead_after_s=dead_after_s)
    params, _ = lm.snapshot_params()
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(5)]
    try:
        futs = [router.submit(p, 6) for p in prompts]
        for p, f in zip(prompts, futs):
            np.testing.assert_array_equal(
                f.result(timeout=120)["result"],
                _oracle(cfg, params, p, 6))
        st = router.stats()
        assert st["requests_lost"] == 0
        assert st["output_mismatches"] == 0
        assert st["deaths"] == 1
        # the rows follow the router's own clock: allow the survivor
        # a few verdict periods to read UP, not a fixed wall time
        deadline = time.monotonic() + 10 * dead_after_s
        while router.replica_rows()[1]["state"] != "UP":
            assert time.monotonic() < deadline, router.replica_rows()
            time.sleep(0.05)
        rows = router.replica_rows()
        assert rows[0]["state"] == "DEAD" and rows[0]["role"] == "prefill"
        assert rows[1]["state"] == "UP"
    finally:
        _stop_disagg(router, replicas, engines)


def test_fleet_unified_roles_never_two_stage(mv_session):
    """Back-compat: an all-unified fleet (the default role) never
    engages the transfer plane — no MSG_XFER, no kv_xfers, identical
    behavior to the pre-disaggregation fleet."""
    from multiverso_tpu.models.transformer import TransformerLM

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    kv, router, replicas, engines = _mk_disagg_fleet(
        "unif", lm, roles=("unified", "unified"))
    params, _ = lm.snapshot_params()
    rng = np.random.default_rng(29)
    try:
        for _ in range(4):
            p = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)
            np.testing.assert_array_equal(
                router.predict(p, 6)["result"], _oracle(cfg, params, p, 6))
        st = router.stats()
        assert st["requests_lost"] == 0
        assert st["kv_xfers"] == 0 and st["kv_bytes_moved"] == 0
        assert replicas[0].xfers_sent == replicas[1].xfers_sent == 0
    finally:
        _stop_disagg(router, replicas, engines)
