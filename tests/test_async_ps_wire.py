"""Unit tests for the async-PS wire format (parallel/async_ps.py).

The cross-process behavior is covered by tests/test_multiprocess.py; these
pin the serialization layer itself — framing, dtype fidelity (incl.
extension dtypes), option round-trip — without spawning processes.
"""

import numpy as np
import pytest

from multiverso_tpu.parallel import async_ps
from multiverso_tpu.quantization import SparseFilter
from multiverso_tpu.updaters import AddOption


def test_wire_trace_context_round_trip():
    """The two trace-id header fields: a publish span's context survives
    serialization (so a consumer's apply span joins the publisher's
    trace), and an untraced record deserializes to ctx=None."""
    from multiverso_tpu import trace

    ids = np.array([1, 2], np.int32)
    vals = np.ones((2, 3), np.float32)
    ctx = trace.SpanContext(trace_id=0xDEADBEEF1234, span_id=0x42)
    data = async_ps._serialize(async_ps.KEYED, 4, None, [ids, vals], ctx)
    *_, ctx2, _, _ = async_ps._deserialize(data)
    assert ctx2 == ctx

    bare = async_ps._serialize(async_ps.KEYED, 4, None, [ids, vals])
    *_, ctx3, _, _ = async_ps._deserialize(bare)
    assert ctx3 is None


def test_dense_record_round_trip():
    opt = AddOption(worker_id=3, learning_rate=0.125, momentum=0.5,
                    rho=0.25, lam=0.0625)
    delta = np.arange(12, dtype=np.float32)
    blobs = SparseFilter(clip=0.0, dtype=np.float32).filter_in([delta])
    data = async_ps._serialize(async_ps.DENSE, 7, opt, blobs)
    (kind, table_id, opt2, arrays, ts, ctx, epoch,
     version) = async_ps._deserialize(data)
    assert (kind, table_id) == (async_ps.DENSE, 7)
    assert (epoch, version) == (0, 0)      # unfenced legacy defaults
    assert opt2.worker_id == 3
    assert opt2.learning_rate == pytest.approx(0.125)
    assert opt2.momentum == pytest.approx(0.5)
    assert opt2.rho == pytest.approx(0.25)
    assert opt2.lam == pytest.approx(0.0625)
    out = SparseFilter(clip=0.0, dtype=np.float32).filter_out(arrays)[0]
    np.testing.assert_array_equal(out, delta)


def test_keyed_record_preserves_dtypes():
    ids = np.array([5, 1, 9], np.int32)
    vals = np.arange(6, dtype=np.float64).reshape(3, 2) * 0.1
    data = async_ps._serialize(async_ps.KEYED, 2, None, [ids, vals])
    (kind, table_id, opt, (ids2, vals2), ts, ctx, _,
     _) = async_ps._deserialize(data)
    assert kind == async_ps.KEYED and table_id == 2
    assert ids2.dtype == np.int32 and vals2.dtype == np.float64
    np.testing.assert_array_equal(ids2, ids)
    np.testing.assert_array_equal(vals2, vals)   # f64 bit-exact
    assert opt.worker_id == 0                    # None option -> defaults


def test_bfloat16_wire_round_trip():
    import ml_dtypes

    arr = np.array([1.5, -2.5, 0.0, 3.0], ml_dtypes.bfloat16)
    data = async_ps._serialize(async_ps.DENSE, 0, None, [arr])
    _, _, _, (out,), _, _, _, _ = async_ps._deserialize(data)
    assert out.dtype == np.dtype(ml_dtypes.bfloat16)
    np.testing.assert_array_equal(out.astype(np.float32),
                                  arr.astype(np.float32))


def test_kv_record():
    keys = np.array([7, -3], np.int64)
    vals = np.array([1.0, 0.5], np.float64)
    data = async_ps._serialize(async_ps.KV, 1, None, [keys, vals])
    kind, table_id, _, (k2, v2), _, _, _, _ = async_ps._deserialize(data)
    assert kind == async_ps.KV
    np.testing.assert_array_equal(k2, keys)
    np.testing.assert_array_equal(v2, vals)


def test_epoch_version_header_round_trip():
    """The fencing fields (PR 14): a fenced publish's (epoch, version)
    survive the wire, and the STATE kind (the fenced restart's absolute
    rebase record) frames like any other record."""
    state = np.arange(6, dtype=np.float32).reshape(2, 3)
    data = async_ps._serialize(async_ps.STATE, 3, None, [state],
                               epoch=7, version=41)
    (kind, table_id, _, (out,), _, ctx, epoch,
     version) = async_ps._deserialize(data)
    assert (kind, table_id) == (async_ps.STATE, 3)
    assert (epoch, version) == (7, 41)
    assert ctx is None
    np.testing.assert_array_equal(out, state)


def test_epoch_fence_highest_wins():
    """EpochFence: unfenced (0) always passes and never advances; a
    lower epoch than the highest seen is rejected and counted."""
    fence = async_ps.EpochFence("test")
    assert fence.admit(0) and fence.epoch == 0
    assert fence.admit(2) and fence.epoch == 2
    assert fence.admit(2)
    assert not fence.admit(1)              # zombie incarnation
    assert fence.admit(0)                  # legacy records still pass
    assert fence.admit(3) and fence.epoch == 3
    assert not fence.admit(2)
    assert fence.rejections == 2


def test_claim_epoch_monotonic():
    class KV:
        def __init__(self):
            self.d = {}

        def key_value_set(self, k, v, allow_overwrite=False):
            self.d[k] = v

        def key_value_try_get(self, k):
            if k not in self.d:
                raise KeyError("NOT_FOUND: " + k)
            return self.d[k]

    kv = KV()
    assert async_ps.claim_epoch(kv) == 1
    assert async_ps.claim_epoch(kv) == 2
    assert async_ps.claim_epoch(kv) == 3


def test_claim_epoch_fails_loudly_on_broken_kv():
    """A fencing-token read error must NOT default to 0: rewinding the
    key would fence out the legitimately restarted trainer forever."""
    import pytest

    from multiverso_tpu.log import FatalError

    class BrokenKV:
        def key_value_try_get(self, k):
            raise RuntimeError("UNAVAILABLE: coordinator flapping")

        def key_value_set(self, k, v, allow_overwrite=False):
            raise AssertionError("must not write after a failed read")

    with pytest.raises(FatalError):
        async_ps.claim_epoch(BrokenKV())


def test_part_records_reassemble_to_one_apply():
    """Wire chunking: PART records at consecutive seqs reassemble into ONE
    logical record and apply exactly once; an out-of-order part is a broken
    transport invariant and fails LOUDLY (applying around it would silently
    diverge the replica — advisor r3)."""
    import pytest

    from multiverso_tpu.log import FatalError

    opt = AddOption(worker_id=1)
    vals = np.arange(64, dtype=np.float32)
    payload = async_ps._serialize(async_ps.KEYED, 5, opt,
                                  [np.arange(8, dtype=np.int32), vals])
    maxb = 16
    n_parts = -(-len(payload) // maxb)
    parts = [async_ps._PART_HEADER.pack(async_ps.PART, i, n_parts)
             + payload[i * maxb:(i + 1) * maxb] for i in range(n_parts)]

    bus = object.__new__(async_ps.AsyncDeltaBus)
    bus._parts = {}
    applied = []
    bus._apply = applied.append
    for p in parts:
        bus._consume(0, p)
    assert applied == [payload]           # one apply, exact bytes
    assert bus._parts[0] == []

    # out-of-order part (index 1 first) = broken consecutive-seq invariant
    with pytest.raises(FatalError):
        bus._consume(0, parts[1])
    assert applied == [payload]           # nothing half-applied

    # non-PART records pass straight through
    bus._parts = {}
    bus._consume(0, payload)
    assert applied == [payload, payload]


def test_sparse_filter_compresses_sparse_dense_payload():
    """A mostly-zero dense delta rides the wire compressed (the reference
    >50%-small rule) and reconstructs exactly."""
    delta = np.zeros(1000, np.float32)
    delta[[3, 500, 999]] = [1.0, -2.0, 0.5]
    f = SparseFilter(clip=0.0, dtype=np.float32)
    blobs = f.filter_in([delta])
    wire = async_ps._serialize(async_ps.DENSE, 0, None, blobs)
    assert len(wire) < delta.nbytes // 2   # actually compressed
    _, _, _, arrays, _, _, _, _ = async_ps._deserialize(wire)
    out = f.filter_out(arrays)[0]
    np.testing.assert_array_equal(out, delta)
