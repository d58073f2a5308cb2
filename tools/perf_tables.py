"""Dense/sparse matrix-table performance harness.

Port of the reference ``TestDensePerf`` / ``TestSparsePerf`` drivers
(``Test/main.cpp:343-497`` in the Multiverso reference): a 1M x 50 float
matrix table, timed rounds of whole-table Get, %-sparse row Add, and Get
again, printing per-op wall times and the Dashboard dump at the end.

Usage:
    python tools/perf_tables.py [dense|sparse|device|lightlda]
                                [-rows=1000000] [-cols=50] [-rounds=10]
                                [-percent=1.0] [-workers=4] [-doc_words=2048]

``lightlda`` drives the sparse-matrix path the way LightLDA drove the
reference (BASELINE config 4): a 1M-row word-topic count table with
``workers`` simulated samplers, each round pushing zipf-distributed
touched-row count deltas (``add_rows`` with per-worker AddOptions — the
server-side dirty-bit update, ``src/table/sparse_matrix_table.cpp:200``)
and pulling only the rows OTHER workers dirtied since its last pull
(``get_dirty_rows`` — ``UpdateGetState``, ``:226``). Prints per-op times,
pushed/pulled row rates and the wire-compression ratio of the touched-row
representation vs a dense whole-table push.

``sparse`` adds only ``percent``%% of rows per round (the touched-row wire
path); ``dense`` adds the whole table. Both move data host<->device every
round, like the reference's user buffers. ``device`` times the jitted
update/lookup programs on pre-staged device arrays — the table-update
bandwidth the chip itself sustains, independent of the host link.
Runs on whatever devices the process sees (one real TPU chip, or CPU with
JAX_PLATFORMS=cpu).
"""

from __future__ import annotations

import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])

import multiverso_tpu as mv
from multiverso_tpu.dashboard import Dashboard


def main(argv) -> int:
    mode = "dense"
    args = []
    for a in argv[1:]:
        if a in ("dense", "sparse", "device", "lightlda"):
            mode = a
        else:
            args.append(a)
    mv.define_int("rows", 1_000_000, "table rows")
    mv.define_int("cols", 50, "table cols")
    mv.define_int("rounds", 10, "timed rounds")
    mv.define_float("percent", 1.0, "rows touched per sparse add (%)")
    mv.define_int("workers", 4, "lightlda: simulated sampler workers")
    mv.define_int("doc_words", 2048, "lightlda: distinct words per push")
    mv.init(["perf"] + args)
    rows, cols = mv.get_flag("rows"), mv.get_flag("cols")
    rounds = mv.get_flag("rounds")

    if mode == "lightlda":
        return _lightlda(rows, cols, rounds)

    table = mv.create_table("matrix", rows, cols, name="perf_matrix")
    rng = np.random.default_rng(0)

    n_touch = max(1, int(rows * mv.get_flag("percent") / 100.0))

    # warm up the host-path jitted ops with the timed shapes (first compile
    # is not the steady state; row ops bucket by id-set size, so warm with
    # n_touch). The device mode warms its own programs inside pipelined()
    # and must not pay host-link round trips here.
    if mode == "dense":
        table.get()
        table.add(np.zeros((rows, cols), np.float32))
    elif mode == "sparse":
        table.get()
        warm_ids = np.arange(n_touch, dtype=np.int32)
        table.add_rows(warm_ids, np.zeros((n_touch, cols), np.float32))
        table.get_rows(warm_ids)

    def timed(label, fn, op_bytes):
        t0 = time.perf_counter()
        for _ in range(rounds):
            fn()
        dt = (time.perf_counter() - t0) / rounds
        print(f"{label:28s} {dt * 1e3:10.2f} ms/round "
              f"({op_bytes / 1e6 / dt:.0f} MB/s)")
        return dt

    print(f"[{mode}] matrix {rows}x{cols} float32 "
          f"({rows * cols * 4 / 1e6:.0f} MB), {rounds} rounds, "
          f"mesh {dict(mv.session().mesh.shape)}")

    table_bytes = rows * cols * 4

    if mode == "device":
        import jax
        import jax.numpy as jnp

        from multiverso_tpu.tables import _rowops
        from multiverso_tpu.tables.base import _option_scalars
        from multiverso_tpu.updaters import AddOption

        opt = _option_scalars(AddOption(), table.dtype)
        delta_dev = jax.device_put(
            rng.standard_normal((rows, cols)).astype(np.float32),
            table.sharding)

        def dev_add():
            table._data, table._ustate = table._apply_fn(
                table._data, table._ustate, delta_dev, *opt)

        ids = rng.choice(rows, size=n_touch, replace=False).astype(np.int32)
        size = _rowops.bucket_size(n_touch)
        padded_ids, rmask = _rowops.pad_ids(ids, n_touch, size)
        padded_vals = _rowops.pad_values(
            rng.standard_normal((n_touch, cols)).astype(np.float32),
            n_touch, size)
        ids_dev = jnp.asarray(padded_ids)
        vals_dev = jnp.asarray(padded_vals)
        mask_dev = jnp.asarray(rmask)

        def dev_add_rows():
            table._data, table._ustate = table._row_apply(
                table._data, table._ustate, ids_dev, vals_dev, mask_dev,
                *opt)

        last_gather = [None]

        def dev_get_rows():
            last_gather[0] = table._row_gather(table._data, ids_dev)

        def drain():
            """Force the queued chain: fetch a scalar that depends on the
            final state, so nothing is still queued when the clock
            stops."""
            src = (last_gather[0] if last_gather[0] is not None
                   else table._data)
            return float(jnp.sum(src[0]))

        def pipelined(label, fn, op_bytes):
            """Queue ``rounds`` dispatches, sync once: measures device
            throughput with per-dispatch latency amortised."""
            fn()                         # compile
            drain()
            t0 = time.perf_counter()
            for _ in range(rounds):
                fn()
            drain()
            dt = (time.perf_counter() - t0) / rounds
            print(f"{label:34s} {dt * 1e3:10.2f} ms/round "
                  f"({op_bytes / 1e6 / dt:.0f} MB/s)")

        touched_bytes = n_touch * cols * 4
        print(f"touched rows per row-op: {n_touch}")
        pipelined("device add (whole table)", dev_add, table_bytes)
        pipelined(f"device add_rows ({mv.get_flag('percent')}% rows)",
                  dev_add_rows, touched_bytes)
        pipelined(f"device get_rows ({mv.get_flag('percent')}% rows)",
                  dev_get_rows, touched_bytes)
        Dashboard.display()
        mv.shutdown()
        return 0

    timed("get (whole table)", table.get, table_bytes)

    if mode == "dense":
        delta = rng.standard_normal((rows, cols)).astype(np.float32)
        timed("add (whole table)", lambda: table.add(delta), table_bytes)
    else:
        ids = rng.choice(rows, size=n_touch, replace=False).astype(np.int32)
        vals = rng.standard_normal((n_touch, cols)).astype(np.float32)
        touched_bytes = n_touch * cols * 4
        print(f"touched rows per add: {n_touch}")
        timed(f"add_rows ({mv.get_flag('percent')}% rows)",
              lambda: table.add_rows(ids, vals), touched_bytes)
        timed(f"get_rows ({mv.get_flag('percent')}% rows)",
              lambda: table.get_rows(ids), touched_bytes)

    timed("get (whole table, after)", table.get, table_bytes)

    Dashboard.display()
    mv.shutdown()
    return 0


def _lightlda(rows: int, cols: int, rounds: int) -> int:
    """LightLDA-shaped sparse workload (reference BASELINE config 4).

    Word-topic count table [vocab, topics]; per round each simulated worker
    pushes count deltas for a zipf "document batch" of distinct words and
    pulls the rows the OTHER workers dirtied — the filtered pull the
    reference implements with per-worker dirty bitmaps + SparseFilter
    (``src/table/sparse_matrix_table.cpp:145-309``).
    """
    import time as _time

    from multiverso_tpu.updaters import AddOption

    workers = mv.get_flag("workers")
    doc_words = mv.get_flag("doc_words")
    table = mv.create_table("matrix", rows, cols, name="word_topic",
                            is_sparse=True, num_sim_workers=workers)
    rng = np.random.default_rng(0)
    # zipf word law over the vocab, like a real corpus
    ranks = np.arange(1, rows + 1)
    probs = 1.0 / ranks
    probs /= probs.sum()

    print(f"[lightlda] word-topic {rows}x{cols} f32, {workers} workers, "
          f"{doc_words} words/push, {rounds} rounds, "
          f"mesh {dict(mv.session().mesh.shape)}")

    # pre-draw each worker/round's word set (host sampling is not the
    # thing under test) + topic count deltas (+1 new topic / -1 old topic)
    pushes = []
    for r in range(rounds):
        per_worker = []
        for w in range(workers):
            ids = np.unique(rng.choice(rows, size=doc_words, p=probs)
                            ).astype(np.int32)
            vals = np.zeros((ids.size, cols), np.float32)
            new_t = rng.integers(0, cols, ids.size)
            old_t = rng.integers(0, cols, ids.size)
            vals[np.arange(ids.size), new_t] += 1.0
            vals[np.arange(ids.size), old_t] -= 1.0
            per_worker.append((ids, vals))
        pushes.append(per_worker)

    # warm the bucketed row ops
    ids0, vals0 = pushes[0][0]
    table.add_rows(ids0, np.zeros_like(vals0), AddOption(worker_id=0))
    for w in range(workers):
        table.get_dirty_rows(w)

    def run_blocking():
        """Reference LightLDA loop shape: push, then a BLOCKING filtered
        pull per worker — every pull pays a full host<->device round
        trip before the next worker proceeds."""
        pushed = pulled = 0
        push_t = pull_t = 0.0
        t0 = _time.perf_counter()
        for r in range(rounds):
            for w in range(workers):
                ids, vals = pushes[r][w]
                t1 = _time.perf_counter()
                table.add_rows(ids, vals, AddOption(worker_id=w))
                push_t += _time.perf_counter() - t1
                pushed += ids.size
            for w in range(workers):
                t1 = _time.perf_counter()
                dirty_ids, dirty_rows = table.get_dirty_rows(w)
                pull_t += _time.perf_counter() - t1
                pulled += dirty_ids.size
        return _time.perf_counter() - t0, push_t, pull_t, pushed, pulled

    def run_pipelined():
        """Reference ``GetPipelineTable`` pattern (``ps_model.cpp:236``)
        on :class:`parallel.PipelinedGetter` (the ``ASyncBuffer``
        double-buffer): round r's pulls run on background threads while
        round r+1's pushes dispatch, and the workers' pulls overlap each
        other — the host-link round trips that dominate the blocking
        loop ride concurrently."""
        from multiverso_tpu.parallel import PipelinedGetter

        getters = [PipelinedGetter(table.get_dirty_rows)
                   for _ in range(workers)]
        pushed = pulled = 0
        t0 = _time.perf_counter()
        for w in range(workers):                   # round 0 pushes
            ids, vals = pushes[0][w]
            table.add_rows(ids, vals, AddOption(worker_id=w))
            pushed += ids.size
        for w in range(workers):                   # start round 0 pulls
            getters[w].prime(w)
        for r in range(1, rounds):
            for w in range(workers):               # overlaps r-1 pulls
                ids, vals = pushes[r][w]
                table.add_rows(ids, vals, AddOption(worker_id=w))
                pushed += ids.size
            for w in range(workers):               # collect r-1, start r
                dirty_ids, _ = getters[w].get(w)
                pulled += dirty_ids.size
        for w in range(workers):                   # collect the last round
            dirty_ids, _ = getters[w].get()
            pulled += dirty_ids.size
        return _time.perf_counter() - t0, pushed, pulled

    total, push_t, pull_t, pushed, pulled = run_blocking()
    p_total, p_pushed, p_pulled = run_pipelined()

    dense_bytes = rows * cols * 4
    # measured mean rows per push (unique zipf draws < doc_words)
    rows_per_push = pushed / (rounds * workers)
    push_bytes = rows_per_push * (cols * 4 + 4)   # touched rows + ids
    print(f"push: {pushed} rows in {push_t:.2f}s "
          f"({pushed / max(push_t, 1e-9):,.0f} rows/s)")
    print(f"filtered pull: {pulled} dirty rows in {pull_t:.2f}s "
          f"({pulled / max(pull_t, 1e-9):,.0f} rows/s)")
    print(f"wire: touched-row push = {push_bytes / 1e6:.1f} MB vs dense "
          f"{dense_bytes / 1e6:.0f} MB ({dense_bytes / push_bytes:,.0f}x "
          f"smaller)")
    print(f"total (blocking): {rounds} rounds x {workers} workers in "
          f"{total:.2f}s ({rounds * workers / total:.1f} "
          f"worker-iterations/s)")
    # background pulls may coalesce two rounds' dirty rows (the pull races
    # the next round's pushes); report both pulled counts so the speedup
    # can be read against equal work — a large delta would mean the win is
    # partly "fewer rows moved", not overlap
    work_delta = abs(pulled - p_pulled) / max(pulled, 1)
    print(f"total (pipelined): {rounds} rounds x {workers} workers in "
          f"{p_total:.2f}s ({rounds * workers / p_total:.1f} "
          f"worker-iterations/s) — {total / p_total:.2f}x vs blocking "
          f"(double-buffered get_dirty_rows; pulled {p_pulled} rows vs "
          f"blocking {pulled}, {work_delta * 100:.1f}% work delta"
          f"{', NOT comparable' if work_delta > 0.05 else ''})")
    # correctness probe: global count conservation (every +1 has a -1,
    # so the table sums to ~0)
    probe = float(np.sum(table.get_rows(np.arange(0, rows,
                                                  max(rows // 4096, 1)))))
    print(f"sampled count-conservation probe: {probe:+.1f}")
    Dashboard.display()
    mv.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
