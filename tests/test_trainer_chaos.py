"""Durable online learning (PR 14): epoch-fenced param plane, staleness-
aware serving, async-PS version monotonicity, and the 3-process trainer
chaos acceptance test (kill mid-publish-stream -> serving fleet flags
STALE but keeps serving -> checkpoint+WAL recovery to the exact
pre-crash version -> fenced republish re-converges -> zombie rejected).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


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

    def blocking_key_value_get_bytes(self, key, timeout_ms):
        return self.blocking_key_value_get(key, timeout_ms)

    def key_value_increment(self, key, amount):
        with self.lock:
            self.d[key] = str(int(self.d.get(key, "0")) + amount)

    def key_value_delete(self, key):
        with self.lock:
            for k in [k for k in self.d
                      if k == key or k.startswith(key + "/")]:
                del self.d[k]


# -- faultinject grammar ------------------------------------------------------

def test_trainer_fault_grammar_parses():
    from multiverso_tpu.serving.faultinject import FaultPlan

    plan = FaultPlan("kill_trainer_at_publish=6,wal_torn_tail=1,"
                     "zombie_epoch=3:1")
    assert plan.kill_trainer_at == 6
    assert plan.wal_fault == "torn_tail"
    assert (plan.zombie_at, plan.zombie_epoch) == (3, 1)
    assert plan.active()
    assert FaultPlan("wal_bad_crc=1").wal_fault == "bad_crc"
    # the documented bare (valueless) forms parse too
    bare = FaultPlan("kill_trainer_at_publish=2,wal_torn_tail")
    assert bare.wal_fault == "torn_tail"
    assert FaultPlan("wal_bad_crc").wal_fault == "bad_crc"
    with pytest.raises(ValueError):
        FaultPlan("zombie_epoch=0:1")
    with pytest.raises(ValueError):
        FaultPlan("wal_torn_tail=maybe")


def test_on_trainer_publish_kills_and_corrupts_wal(tmp_path):
    from multiverso_tpu.io import wal
    from multiverso_tpu.serving.faultinject import FaultPlan

    w = wal.DeltaWAL(str(tmp_path), rank=0)
    from multiverso_tpu.parallel import async_ps
    from multiverso_tpu.updaters import AddOption

    for i in range(1, 4):
        w.append(0, i, async_ps._serialize(
            async_ps.DENSE, 0, AddOption(worker_id=0),
            [np.full(4, float(i), np.float32)], version=i))
    killed = []
    plan = FaultPlan("kill_trainer_at_publish=2,wal_bad_crc=1",
                     kill_fn=lambda: killed.append(True))
    plan.attach_wal(w)
    plan.on_trainer_publish(1)
    assert not killed
    plan.on_trainer_publish(2)
    assert killed and plan.counts["trainer_kills"] == 1
    assert plan.counts["wal_faults"] == 1
    w.close()
    # the staged corruption is exactly what recovery truncates
    stats = wal.recover(str(tmp_path), 0)
    assert stats["truncated_at"] > 0
    assert [v for _, v, _, _ in wal.iter_records(str(tmp_path), 0)] \
        == [1, 2]


def test_zombie_epoch_stamps_stale_publishes():
    from multiverso_tpu.serving.faultinject import FaultPlan

    plan = FaultPlan("zombie_epoch=3:1")
    assert plan.publish_epoch(1, 2) == 2
    assert plan.publish_epoch(2, 2) == 2
    assert plan.publish_epoch(3, 2) == 1      # the zombie takes over
    assert plan.publish_epoch(4, 2) == 1
    assert plan.counts["zombie_publishes"] == 2


# -- param plane (in-process, real sockets) -----------------------------------

def test_param_plane_rebase_fence_and_staleness(mv_session, tmp_path):
    """One process, two transports over real localhost sockets: the
    publisher's STATE rebase + deltas converge a subscriber replica
    bit-exactly with pinned trainer versions; a zombie-epoch record is
    rejected without touching state; silence flags STALE and a fenced
    restart (new epoch, rebase) clears it."""
    import multiverso_tpu as mv
    from multiverso_tpu.parallel.async_ps import DENSE
    from multiverso_tpu.serving import ParamPublisher, ParamSubscriber

    src = mv.create_table("matrix", 6, 4)
    dst = mv.create_table("matrix", 6, 4)
    kv = FakeKV()
    pub = ParamPublisher(kv, 2, label="pp", epoch=2)
    sub = ParamSubscriber(kv, {src.table_id: dst}, rank=1, size=2,
                          label="pp", poll_s=0.01, stale_after_s=0.6)
    try:
        rng = np.random.default_rng(5)
        pub.publish_state(src)
        for _ in range(4):
            d = rng.standard_normal((6, 4)).astype(np.float32)
            src.add(d)
            pub.publish_delta(src, d)
        deadline = time.monotonic() + 30
        while sub.applied < 5 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sub.applied == 5 and sub.states_applied == 1
        assert dst.version == src.version    # pinned trainer identity
        assert dst.epoch == 2
        np.testing.assert_array_equal(dst.get(), src.get())

        # zombie: a stale-epoch record must be rejected, state untouched
        before = dst.get().copy()
        pub.publish_record(DENSE, src.table_id,
                           [np.full((6, 4), 99.0, np.float32)],
                           epoch=1, version=src.version + 1)
        deadline = time.monotonic() + 30
        while (sub.stats()["fence_rejections"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert sub.stats()["fence_rejections"] == 1
        np.testing.assert_array_equal(dst.get(), before)
        assert dst.version == src.version

        # a BACKWARDS epoch-key blip (transient KV failure, operator
        # rewind) must never detach the live stream onto a dead
        # lower-epoch label — highest-epoch-wins, like the fence
        kv.key_value_set("pp/epoch", "1")
        time.sleep(0.5)                      # > the epoch-probe cadence
        assert sub._cur_epoch == 2
        kv.key_value_set("pp/epoch", "2")

        # silence -> STALE; a fenced restart (epoch 3 rebase) clears it
        deadline = time.monotonic() + 30
        while not sub.params_stale() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sub.params_stale()
        pub2 = ParamPublisher(kv, 2, label="pp")    # claims epoch 3
        try:
            assert pub2.epoch == 3
            src.add(np.ones((6, 4), np.float32))
            pub2.publish_state(src)
            deadline = time.monotonic() + 30
            while (sub.stats()["epoch_switches"] < 2
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            deadline = time.monotonic() + 30
            while (dst.version != src.version
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert dst.version == src.version and dst.epoch == 3
            np.testing.assert_array_equal(dst.get(), src.get())
            assert not sub.params_stale()    # recovery is automatic
        finally:
            pub2.stop()
    finally:
        sub.stop()
        pub.stop()


def test_param_plane_kv_table_state_rebase(mv_session):
    """KVTable rides the STATE protocol too: a fenced rebase ships
    keys+vals and installs the exact (version, epoch), and KV delta
    records pin the publisher's version identity."""
    import multiverso_tpu as mv
    from multiverso_tpu.serving import ParamPublisher, ParamSubscriber

    src = mv.create_table("kv")
    dst = mv.create_table("kv")
    kv = FakeKV()
    pub = ParamPublisher(kv, 2, label="ppkv", epoch=1)
    sub = ParamSubscriber(kv, {src.table_id: dst}, rank=1, size=2,
                          label="ppkv", poll_s=0.01)
    try:
        src.add([3, 7], [1.5, 2.5])
        src.add([3], [10.0])
        pub.publish_state(src)
        src.add([9], [4.0])
        pub.publish_kv(src, [9], [4.0])
        deadline = time.monotonic() + 30
        while sub.applied < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sub.applied == 2 and sub.states_applied == 1
        assert dst._store == src._store
        assert dst.version == src.version and dst.epoch == 1
    finally:
        sub.stop()
        pub.stop()


# -- snapshot staleness surface ----------------------------------------------

def test_snapshot_manager_params_age(mv_session):
    import multiverso_tpu as mv
    from multiverso_tpu.serving import SnapshotManager

    t = mv.create_table("array", 8)
    mgr = SnapshotManager.of(t)
    t.add(np.ones(8, np.float32))
    assert mgr.params_age_s() < 0.5
    assert not mgr.params_stale(10.0)
    assert not mgr.params_stale(0.0)         # 0 disables the verdict
    time.sleep(0.12)
    assert mgr.params_age_s() >= 0.1         # silence accrues age
    assert mgr.params_stale(0.05)
    t.add(np.ones(8, np.float32))            # training moved: age resets
    assert mgr.params_age_s() < 0.1
    # snapshot pins carry (epoch, version)
    with t._lock:
        t.epoch = 4
    snap = mgr.publish()
    assert (snap.epoch, snap.version) == (4, t.version)


def test_engine_health_ships_staleness(mv_session):
    """DecodeEngine.health(): snapshot_version + params_age_s +
    params_stale ride the heartbeat surface, and SERVE_PARAMS_AGE
    tracks the gauge."""
    import multiverso_tpu as mv
    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
    from multiverso_tpu.serving import DecodeEngine, DecodeEngineConfig

    cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                            n_layers=1, d_ff=32, max_seq=16)
    lm = TransformerLM(cfg)
    eng = DecodeEngine("stale_probe", lm, DecodeEngineConfig(
        slots=1, max_prompt=4, max_new=4, watchdog=False))
    try:
        h = eng.health()
        assert {"snapshot_version", "snapshot_epoch", "params_age_s",
                "params_stale"} <= set(h)
        assert h["params_stale"] is False    # flag default 0 = disabled
        mv.set_flag("params_stale_after_s", 0.01)
        time.sleep(0.05)
        assert eng.health()["params_stale"] is True
        lm.train_batch(np.array([[1, 2, 3, 4]], np.int32))
        assert eng.health()["params_stale"] is False
        gauge = Dashboard.get_or_create_gauge(
            "SERVE_PARAMS_AGE[stale_probe]")
        assert gauge.get() >= 0.0
    finally:
        mv.set_flag("params_stale_after_s", 0.0)
        eng.stop()


def test_router_replica_rows_ship_snapshot_version():
    """The router's replica rows (and the FLEET_SNAPSHOT_VERSION gauge
    the obs plane ships) surface each replica's served version and
    STALE verdict from its heartbeat health."""
    from multiverso_tpu.dashboard import Dashboard
    from multiverso_tpu.serving.replica import MSG_HB
    from multiverso_tpu.serving.router import UP, FleetConfig, FleetRouter

    Dashboard.reset()
    kv = FakeKV()
    # dead-but-present endpoints: the transport's subscribe loop gets a
    # fast connect-refuse + interruptible backoff instead of parking in
    # the fake KV's 5-s blocking endpoint lookup at stop() time
    kv.key_value_set("rrows/ep/1", "127.0.0.1:9")
    kv.key_value_set("rrows/ep/2", "127.0.0.1:9")
    router = FleetRouter(3, kv, label="rrows", name="rrows",
                         fleet_config=FleetConfig(heartbeat_ms=50))
    try:
        now = time.monotonic()
        with router._lock:
            for rank, (ver, stale) in ((1, (7, False)), (2, (3, True))):
                rep = router._replicas[rank]
                router._handle_locked(rank, {
                    "t": MSG_HB, "node": rank,
                    "health": {"queue_depth": 0, "snapshot_version": ver,
                               "params_stale": stale}}, now, [])
                assert rep.state == UP
        router.tick()
        rows = router.replica_rows()
        assert [(r["snapshot_version"], r["params_stale"])
                for r in rows] == [(7, False), (3, True)]
        assert Dashboard.get_or_create_gauge(
            "FLEET_SNAPSHOT_VERSION[rrows.1]").get() == 7.0
    finally:
        router.stop()
        Dashboard.reset()


# -- async-PS version monotonicity (satellite) --------------------------------

def test_bus_applier_version_monotonic_under_concurrent_streams(
        mv_session):
    """Property test: two publisher ranks' concurrent record streams
    (deltas + a fenced STATE rebase + a zombie lower-version STATE)
    never produce a version regression at the applier, and mark_dead
    mid-stream preserves the invariant while survivors' records keep
    applying."""
    import multiverso_tpu as mv
    from multiverso_tpu import config
    from multiverso_tpu.parallel import async_ps
    from multiverso_tpu.updaters import AddOption

    t = mv.create_table("matrix", 4, 2)
    kv = FakeKV()
    from multiverso_tpu.runtime import Session
    sess = Session.get()

    class SessStub:
        rank, size = 0, 3
        tables = sess.tables

        def table(self, tid):
            return sess.table(tid)

    old_p2p = config.get_flag("async_p2p")
    config.set_flag("async_p2p", False)
    bus = None
    try:
        bus = async_ps.AsyncDeltaBus(SessStub(), kv, 0.002)
        seqs = {1: 0, 2: 0}
        lock = threading.Lock()

        def emit(rank, payload):
            with lock:
                seq = seqs[rank]
                kv.key_value_set_bytes(f"mvps/{rank}/{seq}", payload)
                seqs[rank] = seq + 1
                kv.key_value_increment(f"mvps/{rank}/n", 1)

        observed = []
        regressions = []
        stop = threading.Event()

        def observe():
            while not stop.is_set():
                v = t.version
                if observed and v < observed[-1]:
                    regressions.append((observed[-1], v))
                observed.append(v)
                time.sleep(0.0005)

        obs = threading.Thread(target=observe, daemon=True)
        obs.start()
        rng = np.random.default_rng(9)

        def publisher(rank, n):
            for i in range(n):
                if i == n // 2 and rank == 1:
                    # a fenced rebase mid-stream (epoch 2, high version)
                    host = np.full((4, 2), 7.0, np.float32)
                    emit(rank, async_ps._serialize(
                        async_ps.STATE, t.table_id, None, [host],
                        epoch=2, version=500 + i))
                    # ...followed by a ZOMBIE rebase (epoch 1, LOWER
                    # version): the fence must reject it or the
                    # observer sees the version walk backwards
                    emit(rank, async_ps._serialize(
                        async_ps.STATE, t.table_id, None,
                        [np.zeros((4, 2), np.float32)], epoch=1,
                        version=3))
                emit(rank, async_ps._serialize(
                    async_ps.KEYED, t.table_id,
                    AddOption(worker_id=0),
                    [np.array([i % 4], np.int32),
                     rng.standard_normal((1, 2)).astype(np.float32)],
                    epoch=2))
                time.sleep(0.001)

        n = 25
        pubs = [threading.Thread(target=publisher, args=(r, n),
                                 daemon=True) for r in (1, 2)]
        for p in pubs:
            p.start()
        # declare rank 2 dead mid-stream: the invariant must hold and
        # rank 1's records keep applying
        time.sleep(0.02)
        bus.mark_dead({2})
        for p in pubs:
            p.join(timeout=30)
        deadline = time.monotonic() + 30
        want_rank1 = n + 2                   # deltas + two STATEs
        while time.monotonic() < deadline:
            from multiverso_tpu.parallel.async_ps import _consumed

            if _consumed.get(1, 0) >= want_rank1:
                break
            time.sleep(0.01)
        stop.set()
        obs.join(timeout=5)
        assert regressions == [], regressions
        from multiverso_tpu.parallel.async_ps import _consumed

        assert _consumed[1] == want_rank1    # survivor fully applied
        assert bus._fence.rejections >= 1    # the zombie was rejected
        assert bus._fence.epoch == 2
        assert t.version > 500               # rebase version installed
        # the observer may be scheduler-starved off the very last apply
        # on a loaded 2-CPU box — the invariant is monotonicity (no
        # regression, asserted above) and never seeing a FUTURE value
        assert max(observed) <= t.version
    finally:
        if bus is not None:
            # surgical teardown: stop() is collective (drain barriers
            # would wait on fake peers) — stop the thread and clear the
            # module counters the next in-process bus would inherit
            bus._stop.set()
            bus._thread.join(timeout=10)
            with async_ps._state_lock:
                if async_ps._active_bus is bus:
                    async_ps._active_bus = None
                async_ps._published = 0
                async_ps._consumed.clear()
        config.set_flag("async_p2p", old_p2p)


# -- the 3-process acceptance test --------------------------------------------

_FILEKV = textwrap.dedent("""
    import os, time

    class FileKV:
        def __init__(self, root):
            self.root = root
        def _p(self, key):
            return os.path.join(self.root, "kv", key.replace("/", "_"))
        def key_value_set(self, key, val, allow_overwrite=False):
            p = self._p(key); tmp = p + f".tmp{os.getpid()}"
            with open(tmp, "w") as f:
                f.write(str(val))
            os.replace(tmp, p)
        def blocking_key_value_get(self, key, timeout_ms):
            deadline = time.monotonic() + timeout_ms / 1000.0
            while True:
                try:
                    with open(self._p(key)) as f:
                        return f.read()
                except FileNotFoundError:
                    if time.monotonic() > deadline:
                        raise TimeoutError(key)
                    time.sleep(0.02)
        def key_value_try_get(self, key):
            try:
                with open(self._p(key)) as f:
                    return f.read()
            except FileNotFoundError:
                raise KeyError("NOT_FOUND: " + key)
""")

_DELTA = textwrap.dedent("""
    import numpy as np

    def make_delta(i):
        rng = np.random.default_rng(1000 + i)
        return rng.standard_normal((6, 4)).astype(np.float32)
""")

_REPLICA = textwrap.dedent("""
    import json, os, sys, time
    sys.path.insert(0, %(repo)r)
    import numpy as np
    %(filekv)s
    rank = int(os.environ["TC_RANK"]); root = os.environ["TC_ROOT"]
    import multiverso_tpu as mv
    mv.init(["w", "-log_level=error", "-params_stale_after_s=1.0"])
    from multiverso_tpu.serving import ParamSubscriber, SnapshotManager

    t = mv.create_table("matrix", 6, 4)
    kv = FileKV(root)
    sub = ParamSubscriber(kv, [t], rank=rank, size=3, label="tchaos",
                          poll_s=0.01)
    mgr = SnapshotManager.of(t)
    print(f"SUB{rank}_UP", flush=True)
    status = os.path.join(root, f"replica{rank}.status")
    while True:
        # the serving claim: snapshot reads must keep answering even
        # while the publish stream is dead
        snap = mgr.ensure_fresh(0.05)
        st = sub.stats()
        st.update({"t": time.time(),
                   "served_version": snap.version,
                   "served_epoch": snap.epoch,
                   "served_sum": float(np.asarray(snap.value).sum()),
                   "mgr_age_s": mgr.params_age_s(),
                   "mgr_stale": mgr.params_stale(1.0)})
        tmp = status + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(st, f)
        os.replace(tmp, status)
        try:
            kv.key_value_try_get("phase/done")
            break
        except KeyError:
            pass
        time.sleep(0.05)
    np.save(os.path.join(root, f"replica{rank}_final.npy"),
            np.asarray(t.get()))
    sub.stop()
    mv.shutdown()
    print(f"SUB{rank}_CLEAN_EXIT", flush=True)
""")

_TRAINER_1 = textwrap.dedent("""
    import json, os, sys, time
    sys.path.insert(0, %(repo)r)
    import numpy as np
    %(filekv)s
    %(delta)s
    root = os.environ["TC_ROOT"]
    import multiverso_tpu as mv
    mv.init(["w", "-log_level=error", "-wal=true",
             "-wal_dir=" + os.path.join(root, "wal"),
             "-chaos=kill_trainer_at_publish=6", "-chaos_seed=1"])
    from multiverso_tpu.io.checkpoint import Autosaver
    from multiverso_tpu.runtime import Session
    from multiverso_tpu.serving import ParamPublisher
    from multiverso_tpu.serving.faultinject import FaultPlan

    t = mv.create_table("matrix", 6, 4)
    kv = FileKV(root)
    plan = FaultPlan.from_flags()
    plan.attach_wal(Session.get().wal)
    pub = ParamPublisher(kv, 3, label="tchaos", chaos=plan)  # epoch 1
    saver = Autosaver(os.path.join(root, "ckpt"), every_steps=3, keep=2)
    pub.publish_state(t)                       # publish 1 (version 0)
    acks = os.path.join(root, "acks.log")
    for i in range(12):
        t.add(make_delta(i))                   # acknowledged + journaled
        with open(acks, "a") as f:
            f.write(f"{i}\\n")
            f.flush()
            os.fsync(f.fileno())
        saver.step(i + 1)
        time.sleep(0.15)                       # let subscribers drain
        pub.publish_delta(t, make_delta(i))    # publish i+2; killed at 6
    print("TRAINER1_UNEXPECTED_SURVIVAL", flush=True)
""")

_TRAINER_2 = textwrap.dedent("""
    import json, os, sys, time
    sys.path.insert(0, %(repo)r)
    import numpy as np
    %(filekv)s
    %(delta)s
    root = os.environ["TC_ROOT"]
    import multiverso_tpu as mv
    mv.init(["w", "-log_level=error", "-wal=true",
             "-wal_dir=" + os.path.join(root, "wal")])
    from multiverso_tpu.io import checkpoint
    from multiverso_tpu.parallel.async_ps import DENSE
    from multiverso_tpu.serving import ParamPublisher

    t = mv.create_table("matrix", 6, 4)
    kv = FileKV(root)
    step = checkpoint.restore_latest(os.path.join(root, "ckpt"))
    acked = len(open(os.path.join(root, "acks.log")).read().split())
    # fault-free oracle: a second table applying every ACKNOWLEDGED
    # delta through the same apply path — recovery must be bit-identical
    oracle = mv.create_table("matrix", 6, 4)
    for i in range(acked):
        from multiverso_tpu.updaters import AddOption
        oracle._apply_dense(make_delta(i), AddOption(worker_id=0))
    bit_identical = bool(np.array_equal(np.asarray(t.get()),
                                        np.asarray(oracle.get())))
    status = {
        "restored_step": step,
        "acked": acked,
        "version": int(t.version),
        "updates_lost": acked - int(t.version),
        "bit_identical": bit_identical,
        "wal_replay": checkpoint.LAST_WAL_REPLAY,
    }
    with open(os.path.join(root, "trainer2.status"), "w") as f:
        json.dump(status, f)
    assert status["updates_lost"] == 0, status
    assert bit_identical, status
    pub = ParamPublisher(kv, 3, label="tchaos")   # claims epoch 2
    assert pub.epoch == 2, pub.epoch
    pub.publish_state(t)                          # fenced rebase
    for i in range(acked, acked + 4):             # training continues
        t.add(make_delta(i))
        pub.publish_delta(t, make_delta(i))
    with open(os.path.join(root, "trainer2.trained"), "w") as f:
        json.dump({"version": int(t.version)}, f)
    kv.blocking_key_value_get("phase/zombie", 300_000)
    # the paused-then-resumed zombie: one stale-epoch record that must
    # be rejected fleet-wide (NOT applied locally either)
    pub.publish_record(DENSE, t.table_id,
                       [np.full((6, 4), 99.0, np.float32)],
                       epoch=1, version=int(t.version) + 1)
    np.save(os.path.join(root, "trainer_final.npy"),
            np.asarray(t.get()))
    with open(os.path.join(root, "trainer2.done"), "w") as f:
        json.dump({"version": int(t.version)}, f)
    kv.blocking_key_value_get("phase/done", 300_000)
    pub.stop()
    mv.shutdown()
    print("TRAINER2_CLEAN_EXIT", flush=True)
""")


def _spawn(tmp_path, script, rank=0):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "TC_RANK": str(rank),
                "TC_ROOT": str(tmp_path),
                "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    return subprocess.Popen([sys.executable, "-c", script], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _read_status(tmp_path, name):
    try:
        with open(os.path.join(str(tmp_path), name)) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def test_trainer_chaos_three_process_acceptance(tmp_path):
    """The acceptance loop: trainer killed mid-publish-stream ->
    subscriber fleet keeps serving and flags STALE -> restarted trainer
    recovers the exact pre-crash state (checkpoint watermark + WAL
    replay; updates_lost 0, bit-identical to the fault-free oracle) ->
    fenced epoch-2 republish re-converges every replica and clears the
    staleness -> a zombie epoch-1 publish is rejected fleet-wide."""
    from multiverso_tpu.serving.faultinject import KILL_EXIT

    os.makedirs(tmp_path / "kv")
    fmt = {"repo": _REPO, "filekv": _FILEKV, "delta": _DELTA}
    subs = {r: _spawn(tmp_path, _REPLICA % fmt, rank=r) for r in (1, 2)}
    trainer2 = None
    outs = {}
    try:
        # replicas up (status files flowing) BEFORE the trainer starts
        deadline = time.monotonic() + 180
        while not all(_read_status(tmp_path, f"replica{r}.status")
                      for r in (1, 2)):
            assert time.monotonic() < deadline
            for r, p in subs.items():
                assert p.poll() is None, (r, p.communicate()[0][-4000:])
            time.sleep(0.05)

        trainer1 = _spawn(tmp_path, _TRAINER_1 % fmt)
        outs["t1"] = trainer1.communicate(timeout=240)[0]
        # the seeded kill fired mid-stream (before the 6th publish hit
        # the wire): 5 acknowledged adds, the 5th's publish lost
        assert trainer1.returncode == KILL_EXIT, outs["t1"][-4000:]
        assert "UNEXPECTED_SURVIVAL" not in outs["t1"]
        t_kill = time.monotonic()
        acked = len(open(os.path.join(str(tmp_path),
                                      "acks.log")).read().split())
        assert acked == 5

        # fleet keeps serving and flags STALE within the threshold
        flagged = {}
        deadline = time.monotonic() + 60
        while len(flagged) < 2:
            assert time.monotonic() < deadline, \
                [_read_status(tmp_path, f"replica{r}.status")
                 for r in (1, 2)]
            for r in (1, 2):
                st = _read_status(tmp_path, f"replica{r}.status")
                if (r not in flagged and st
                        and st["mgr_stale"] and st["params_stale"]):
                    flagged[r] = (time.monotonic() - t_kill,
                                  st["mgr_age_s"])
            time.sleep(0.05)
        for r, (wall_s, age) in flagged.items():
            assert age >= 1.0, (r, flagged)   # threshold respected
        # ...and they are STILL serving (fresh status, snapshot reads)
        for r in (1, 2):
            st = _read_status(tmp_path, f"replica{r}.status")
            assert time.time() - st["t"] < 10, st
            assert st["served_version"] >= 0

        # restart: recovery must be exact, then the fenced republish
        # re-converges the fleet and clears the staleness
        trainer2 = _spawn(tmp_path, _TRAINER_2 % fmt)
        deadline = time.monotonic() + 180
        trained = None
        while trained is None:
            assert time.monotonic() < deadline
            assert trainer2.poll() is None, \
                trainer2.communicate()[0][-4000:]
            trained = _read_status(tmp_path, "trainer2.trained")
            time.sleep(0.05)
        st2 = _read_status(tmp_path, "trainer2.status")
        assert st2["updates_lost"] == 0, st2
        assert st2["bit_identical"], st2
        assert st2["version"] == acked
        assert st2["wal_replay"]["replayed"] >= 1
        assert st2["wal_replay"]["dropped"] == 0

        deadline = time.monotonic() + 60
        while True:
            sts = [_read_status(tmp_path, f"replica{r}.status")
                   for r in (1, 2)]
            if all(st and st["table_versions"].get("0")
                   == trained["version"]
                   and st["epoch"] == 2 and not st["mgr_stale"]
                   and not st["params_stale"] for st in sts):
                break
            assert time.monotonic() < deadline, sts
            time.sleep(0.05)

        # zombie: the dead incarnation's late publish is rejected
        # everywhere and moves nothing
        FileKVWriter = os.path.join(str(tmp_path), "kv",
                                    "phase_zombie")
        with open(FileKVWriter, "w") as f:
            f.write("1")
        deadline = time.monotonic() + 60
        while True:
            sts = [_read_status(tmp_path, f"replica{r}.status")
                   for r in (1, 2)]
            if all(st and st["fence_rejections"] >= 1 for st in sts):
                break
            assert time.monotonic() < deadline, sts
            time.sleep(0.05)
        for st in sts:
            assert st["table_versions"].get("0") == trained["version"]
    finally:
        with open(os.path.join(str(tmp_path), "kv", "phase_done"),
                  "w") as f:
            f.write("1")
        for name, p in list(subs.items()) + [("t2", trainer2)]:
            if p is None:
                continue
            try:
                outs[name] = p.communicate(timeout=90)[0]
            except subprocess.TimeoutExpired:
                p.kill()
                outs[name] = "TIMEOUT: " + p.communicate()[0]
    for r in (1, 2):
        assert subs[r].returncode == 0, f"sub {r}:\n{outs[r][-4000:]}"
        assert f"SUB{r}_CLEAN_EXIT" in outs[r]
    assert trainer2.returncode == 0, outs["t2"][-4000:]
    # the whole fleet converged BIT-IDENTICALLY on the recovered,
    # fenced state (zombie excluded)
    want = np.load(os.path.join(str(tmp_path), "trainer_final.npy"))
    for r in (1, 2):
        got = np.load(os.path.join(str(tmp_path),
                                   f"replica{r}_final.npy"))
        assert np.array_equal(got, want), r
