"""Quantized serving: int8 per-block-scaled KV pools, int8 decode param
pins, and the compressed/quantized ``mvparam`` wire.

The acceptance contract (docs/SERVING.md "Quantized KV & params"):

* **kv_quant=none is bit-identical** — the default engine's outputs and
  stats surface are exactly the pre-quant engine's (the oracle tests in
  test_decode_engine.py run that path; here we assert the quant keys
  stay ABSENT when quant is off);
* **int8 quality is measured, not assumed** — the quant engine's
  argmax-match rate vs the fp32 engine on the same prompts is computed
  by the harness and surfaced through ``record_argmax_match`` into
  ``stats()["argmax_match_rate"]`` (the bench archives it as _info);
* **one-trace invariant survives quantization** — scale arrays ride as
  traced data: 1 step trace, 0 retraces, pin memoization intact;
* **the wire codec is transparent** — subscribers decode by array
  count + trailing dtype, so filtered/quantized publishers converge
  replicas without any flag agreement;
* **cross-mode transfer degrades, never corrupts** — an int8 payload at
  an fp replica (or vice versa) is skipped whole and the receiver
  re-prefills locally (the chain seed is encoding-tagged).
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _small_cfg(**kw):
    from multiverso_tpu.models.transformer import TransformerConfig

    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_seq=48)
    base.update(kw)
    return TransformerConfig(**base)


def _argmax_match(a, b) -> float:
    """Token-level agreement between two generations (the quant quality
    metric): matches over the longer length — a length mismatch counts
    its tail as misses."""
    a, b = np.asarray(a), np.asarray(b)
    n = min(a.size, b.size)
    m = max(a.size, b.size)
    if m == 0:
        return 1.0
    return float((a[:n] == b[:n]).sum()) / m


# -- wire codec (pure functions) ----------------------------------------------

def test_wire_codec_dense_roundtrips():
    from multiverso_tpu.serving.param_plane import (decode_dense,
                                                    encode_dense)

    rng = np.random.default_rng(0)
    shape = (6, 4)
    sparse = np.zeros(shape, np.float32)
    sparse[0, 1] = 1.5
    dense = rng.standard_normal(shape).astype(np.float32)
    for host in (sparse, dense):
        # raw: one array, exact
        arrays = encode_dense(host, compress=False, quant="none")
        assert len(arrays) == 1
        np.testing.assert_array_equal(
            decode_dense(arrays, host.dtype, shape), host)
        # filtered: lossless whether or not compression was profitable
        arrays = encode_dense(host, compress=True, quant="none")
        assert np.asarray(arrays[-1]).dtype == np.int64
        np.testing.assert_array_equal(
            decode_dense(arrays, host.dtype, shape), host)
    # int8 quant: lossy, bounded by half a quant step
    arrays = encode_dense(dense, compress=True, quant="int8")
    assert arrays[0].dtype == np.int8
    assert np.asarray(arrays[-1]).dtype == np.float32
    out = decode_dense(arrays, dense.dtype, shape)
    step = float(np.asarray(arrays[-1]).ravel()[0])
    np.testing.assert_allclose(out, dense, atol=step / 2 + 1e-7)


def test_wire_codec_keyed_roundtrips():
    from multiverso_tpu.serving.param_plane import (decode_keyed,
                                                    encode_keyed)

    rng = np.random.default_rng(1)
    ids = np.array([3, 9, 11], np.int32)
    vals = rng.standard_normal((3, 4)).astype(np.float32)
    # raw
    arrays = encode_keyed(ids, vals, compress=False, quant="none")
    assert len(arrays) == 2
    oid, oval = decode_keyed(arrays, vals.dtype)
    np.testing.assert_array_equal(oid, ids)
    np.testing.assert_array_equal(oval, vals)
    # filtered (sparse vals -> actually compressed; lossless)
    sv = np.zeros((3, 4), np.float32)
    sv[1, 2] = 2.5
    arrays = encode_keyed(ids, sv, compress=True, quant="none")
    assert len(arrays) == 3
    assert np.asarray(arrays[-1]).dtype == np.int64
    oid, oval = decode_keyed(arrays, sv.dtype)
    np.testing.assert_array_equal(oid, ids)
    np.testing.assert_array_equal(oval.reshape(sv.shape), sv)
    # int8 quant
    arrays = encode_keyed(ids, vals, compress=True, quant="int8")
    assert len(arrays) == 3 and arrays[1].dtype == np.int8
    assert np.asarray(arrays[-1]).dtype == np.float32
    oid, oval = decode_keyed(arrays, vals.dtype)
    step = float(np.asarray(arrays[-1]).ravel()[0])
    np.testing.assert_allclose(oval, vals, atol=step / 2 + 1e-7)


# -- param plane over the wire ------------------------------------------------

class FakeKV:
    """In-process coordination-KV fake (strings + bytes + counters)."""

    def __init__(self):
        self.d = {}
        self.lock = threading.Lock()

    def key_value_set(self, key, val, allow_overwrite=False):
        with self.lock:
            self.d[key] = str(val)

    def key_value_set_bytes(self, key, val):
        with self.lock:
            self.d[key] = bytes(val)

    def key_value_try_get(self, key):
        with self.lock:
            if key not in self.d:
                raise KeyError("NOT_FOUND: " + key)
            return self.d[key]

    def blocking_key_value_get(self, key, timeout_ms):
        deadline = time.monotonic() + timeout_ms / 1000.0
        while True:
            with self.lock:
                if key in self.d:
                    return self.d[key]
            if time.monotonic() > deadline:
                raise TimeoutError(key)
            time.sleep(0.005)


def test_param_plane_compressed_wire_converges_bit_exact(mv_session):
    """Default wire (param_wire_compress=on): sparse deltas ship
    filtered, the subscriber decodes transparently, replicas converge
    bit-exactly, and the publisher's ledger shows the compression."""
    import multiverso_tpu as mv
    from multiverso_tpu.serving import ParamPublisher, ParamSubscriber

    src = mv.create_table("matrix", 8, 4)
    dst = mv.create_table("matrix", 8, 4)
    kv = FakeKV()
    pub = ParamPublisher(kv, 2, label="qw", epoch=1, wire_compress=True)
    sub = ParamSubscriber(kv, {src.table_id: dst}, rank=1, size=2,
                          label="qw", poll_s=0.01)
    try:
        pub.publish_state(src)
        for i in range(4):
            d = np.zeros((8, 4), np.float32)
            d[i, i % 4] = float(i + 1)        # ~97% zero: compresses
            src.add(d)
            pub.publish_delta(src, d)
        src.add_rows([2, 5], np.ones((2, 4), np.float32))
        pub.publish_keyed(src, np.array([2, 5], np.int32),
                          np.ones((2, 4), np.float32))
        deadline = time.monotonic() + 30
        while sub.applied < 6 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sub.applied == 6
        np.testing.assert_array_equal(dst.get(), src.get())
        st = pub.stats()
        assert st["publish_bytes"] > 0
        assert 0.0 < st["wire_compressed_ratio"] < 1.0
    finally:
        sub.stop()
        pub.stop()


def test_param_plane_int8_wire_converges_approximately(mv_session):
    """Opt-in lossy wire (param_wire_quant=int8): deltas ship as int8 +
    scale, the subscriber dequantizes, and the replica tracks the
    source within one quant step per applied delta."""
    import multiverso_tpu as mv
    from multiverso_tpu.serving import ParamPublisher, ParamSubscriber

    src = mv.create_table("matrix", 6, 4)
    dst = mv.create_table("matrix", 6, 4)
    kv = FakeKV()
    pub = ParamPublisher(kv, 2, label="qw8", epoch=1,
                         wire_compress=True, wire_quant="int8")
    sub = ParamSubscriber(kv, {src.table_id: dst}, rank=1, size=2,
                          label="qw8", poll_s=0.01)
    try:
        pub.publish_state(src)          # STATE rebases always ship raw
        rng = np.random.default_rng(3)
        steps = []
        for _ in range(3):
            d = rng.standard_normal((6, 4)).astype(np.float32)
            src.add(d)
            pub.publish_delta(src, d)
            steps.append(float(np.abs(d).max()) / 127.0)
        deadline = time.monotonic() + 30
        while sub.applied < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sub.applied == 4
        np.testing.assert_allclose(
            dst.get(), src.get(), atol=sum(steps) / 2 + 1e-6)
        assert dst.version == src.version
    finally:
        sub.stop()
        pub.stop()


def test_param_publisher_rejects_unknown_quant(mv_session):
    from multiverso_tpu.log import FatalError
    from multiverso_tpu.serving import ParamPublisher

    with pytest.raises(FatalError):
        ParamPublisher(FakeKV(), 2, label="qbad", epoch=1,
                       wire_quant="int4")


# -- int8 KV engine -----------------------------------------------------------

def _run_engine(eng, prompts, max_new):
    outs = []
    for p in prompts:
        outs.append(np.asarray(
            eng.submit(p, max_new).result(timeout=120)["result"]))
    return outs


def test_kv_quant_engine_quality_and_invariants(mv_session):
    """The tentpole A/B: an int8 engine serves the same trace as the fp
    engine with a measured argmax-match rate, ONE compiled step, zero
    retraces, a memoized pin, and the quant stats keys present."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    kw = dict(slots=2, max_prompt=16, max_new=8, kv_block_size=4,
              prefill_token_budget=4, prefix_cache=True, watchdog=False)
    fp = srv.register_decoder("fp", lm, **kw)
    q = srv.register_decoder("q", lm, kv_quant="int8", **kw)
    fp.warmup()
    q.warmup()

    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (8, 10, 3, 12, 5)]
    fp_out = _run_engine(fp, prompts, 6)
    q_out = _run_engine(q, prompts, 6)
    rates = [_argmax_match(a, b) for a, b in zip(fp_out, q_out)]
    rate = float(np.mean(rates))
    # int8 KV noise can flip a near-tie argmax; wholesale divergence
    # means the write path is wrong (the smoke threshold, not a claim
    # about large models — the bench archives the real number)
    assert rate >= 0.7, rates
    q.record_argmax_match(rate)

    st = q.stats()
    assert st["kv_quant"] == "int8"
    assert st["argmax_match_rate"] == pytest.approx(rate)
    # every block that held data carries a nonzero scale; released
    # blocks park in the cached tier with their scales intact
    assert st["quant_scale_blocks"] > 0
    assert st["decode_step_retraces"] == 0
    assert st["step_traces"] == 1
    assert st["prefill_traces"] == 1
    assert st["pin_copies"] == 1
    # quantized footprint: int8 + scales is ~4x under fp32
    assert st["kv_bytes_per_device"] < fp.stats()["kv_bytes_per_device"] / 3
    q._pool.check()
    assert q.pool_drift() is None


def test_kv_quant_off_stats_surface_unchanged(mv_session):
    """The metrics-regression contract: a default engine's stats dict
    carries NO quant keys (byte-identical surface to the pre-quant
    engine)."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    lm = TransformerLM(_small_cfg())
    srv = InferenceServer("t")
    eng = srv.register_decoder(
        "plain", lm, slots=2, max_prompt=16, max_new=4, kv_block_size=4,
        prefill_token_budget=4, watchdog=False)
    st = eng.stats()
    for key in ("kv_quant", "quant_scale_blocks", "argmax_match_rate",
                "decode_param_quant"):
        assert key not in st


def test_param_quant_pin_memoized_and_serving(mv_session):
    """decode_param_quant=int8: the engine serves with quantized pins
    (high agreement with fp on a small model), the host-side quant runs
    once per version (pin_copies memoized across waves), and the step
    never retraces (the dequant is folded at compile time)."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    kw = dict(slots=2, max_prompt=16, max_new=8, kv_block_size=4,
              prefill_token_budget=4, watchdog=False)
    fp = srv.register_decoder("fp2", lm, **kw)
    pq = srv.register_decoder("pq", lm, decode_param_quant="int8", **kw)
    fp.warmup()
    pq.warmup()
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (8, 5, 11)]
    fp_out = _run_engine(fp, prompts, 6)
    pq_out = _run_engine(pq, prompts, 6)       # wave 1
    _run_engine(pq, prompts, 6)                # wave 2: same pin
    rate = float(np.mean(
        [_argmax_match(a, b) for a, b in zip(fp_out, pq_out)]))
    assert rate >= 0.7
    st = pq.stats()
    assert st["decode_param_quant"] == "int8"
    assert st["pin_copies"] == 1               # quant ran once, memoized
    assert st["decode_step_retraces"] == 0
    assert st["step_traces"] == 1


def test_quantize_decode_params_shapes():
    from multiverso_tpu.serving.snapshot import quantize_decode_params

    tree = {"w": np.ones((4, 8), np.float32) * 3.0,
            "b": np.arange(8, dtype=np.float32)}
    q = quantize_decode_params(tree)
    assert q["w"]["q"].dtype == np.int8
    assert q["w"]["s"].shape == (1, 8)      # per-output-column
    assert q["b"]["q"].dtype == np.int8
    assert q["b"]["s"].shape == (1,)        # per-tensor for vectors
    np.testing.assert_allclose(
        q["w"]["q"].astype(np.float32) * q["w"]["s"], tree["w"],
        rtol=1e-2)


# -- quantized KV transfer ----------------------------------------------------

def test_quant_disagg_transfer_and_cross_mode_degrade(mv_session):
    """int8 prefill -> int8 decode splices and serves (bytes ~4x under
    the fp payload); a quant payload at an fp replica — and an fp
    payload at a quant replica — is SKIPPED whole (encoding-tagged
    chain seed), and the receiver's own admission re-prefills: a
    config-drifted fleet costs latency, never correctness."""
    from multiverso_tpu.models.transformer import TransformerLM
    from multiverso_tpu.serving import InferenceServer
    from multiverso_tpu.serving import kv_transfer as kt

    cfg = _small_cfg()
    lm = TransformerLM(cfg)
    srv = InferenceServer("t")
    kw = dict(slots=2, max_prompt=16, max_new=8, kv_block_size=4,
              prefill_token_budget=4, prefix_cache=True, watchdog=False)
    pf_q = srv.register_decoder("pfq", lm, kv_quant="int8", **kw)
    dec_q = srv.register_decoder("decq", lm, kv_quant="int8", **kw)
    pf_f = srv.register_decoder("pff", lm, **kw)
    dec_f = srv.register_decoder("decf", lm, **kw)
    for e in (pf_q, dec_q, pf_f, dec_f):
        e.warmup()

    rng = np.random.default_rng(13)
    p = rng.integers(1, cfg.vocab_size, 8).astype(np.int32)  # 2 blocks

    # same-mode quant transfer: splices, serves, ships int8 + scales
    pay_q = pf_q.submit_prefill(p).result(timeout=120)["xfer"]
    assert pay_q["dtype"] == "int8"
    rec = next(iter(pay_q["blocks"].values()))
    assert "ks" in rec and "vs" in rec
    ks, vs = kt.unpack_scales(rec, cfg.n_layers)
    assert ks.shape == (cfg.n_layers,) and (ks > 0).all()
    pay_f = pf_f.submit_prefill(p).result(timeout=120)["xfer"]
    assert kt.payload_bytes(pay_q) < kt.payload_bytes(pay_f) / 3
    info = dec_q.splice(pay_q)
    assert "skipped" not in info and info["xfer_blocks"] == 2
    out_xfer = dec_q.submit(p, 6, xfer_info=info).result(
        timeout=120)["result"]
    # oracle: the quant engine's own unified output (transfer must not
    # change quant results; fp-vs-quant drift is the OTHER test's topic)
    out_uni = np.asarray(pf_q.submit(p, 6).result(timeout=120)["result"])
    np.testing.assert_array_equal(np.asarray(out_xfer), out_uni)
    assert dec_q.stats()["prefill_tokens_saved"] >= 8

    # cross-mode: quant payload at fp replica — seed check skips whole
    info = dec_f.splice(pay_q)
    assert "skipped" in info and info["xfer_blocks"] == 0
    # ...and fp payload at quant replica
    info = dec_q.splice(pay_f)
    assert "skipped" in info and info["xfer_blocks"] == 0
    # the skipped replica still serves the prompt via local re-prefill
    out_f = np.asarray(dec_f.submit(p, 6).result(timeout=120)["result"])
    want = np.asarray(pf_f.submit(p, 6).result(timeout=120)["result"])
    np.testing.assert_array_equal(out_f, want)

    # chaos drop on a quant payload: header + hashes survive, nothing
    # splices, accounting stays zero
    info = dec_q.splice(kt.drop_blocks(
        pf_q.submit_prefill(p).result(timeout=120)["xfer"]))
    assert info["xfer_blocks"] == 0 and "skipped" not in info
    # a scale-stripped record is undecodable: the walk stops there
    pay_bad = pf_q.submit_prefill(
        rng.integers(1, cfg.vocab_size, 8).astype(np.int32)).result(
            timeout=120)["xfer"]
    for blk in pay_bad["blocks"].values():
        blk.pop("ks", None)
        blk.pop("vs", None)
    info = dec_q.splice(pay_bad)
    assert info["xfer_blocks"] == 0
    for e in (pf_q, dec_q, pf_f, dec_f):
        e._pool.check()
        assert e.pool_drift() is None
        assert e.stats()["decode_step_retraces"] == 0
