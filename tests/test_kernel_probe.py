"""Guard the w2v kernel-probe kernels (tools/w2v_kernel_probe.py).

The probe's on-chip prices (docs/W2V_KERNEL.md "4 October 2026")
rests on these kernels being CORRECT — a wrong kernel would time the
wrong thing. The TPU asserts correctness before timing; this suite
keeps the same checks green on CPU (Pallas interpret mode) so a kernel
edit can't silently invalidate the published numbers between on-chip
runs. Shapes are shrunk via the module constants (monkeypatched — the
kernels read them at trace time) because interpret mode executes the
per-row loops in Python.
"""

from __future__ import annotations

import numpy as np
import pytest

import tools.w2v_kernel_probe as kp


@pytest.fixture()
def small_shapes(monkeypatch):
    monkeypatch.setattr(kp, "CHUNK", 32)
    monkeypatch.setattr(kp, "DEPTH", 4)
    return 96, 128          # vocab rows (multiple of TILE), n indices


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_tile_gather_matches_take(small_shapes, dtype):
    import jax.numpy as jnp

    vocab, n = small_shapes
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((vocab, kp.DIM)), dtype)
    # force duplicates AND tile-sharing neighbours — the workload shape
    idx = jnp.asarray(
        np.concatenate([rng.integers(0, vocab, n - 8),
                        np.full(8, 3)]).astype(np.int32))
    out = kp.pallas_gather(table, idx, interpret=True)
    ref = jnp.take(table, idx, axis=0).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(out - ref))) == 0.0


def test_tile_rmw_matches_scatter_add_with_duplicates(small_shapes):
    import jax.numpy as jnp

    vocab, n = small_shapes
    rng = np.random.default_rng(1)
    table = jnp.asarray(rng.standard_normal((vocab, kp.DIM)), jnp.float32)
    # heavy duplication: every update lands in a handful of tiles, the
    # case a pipelined RMW would race on and the serial kernel must get
    # exactly right (up to f32 accumulation order)
    idx = jnp.asarray(rng.integers(0, 16, n).astype(np.int32))
    grads = jnp.asarray(rng.standard_normal((n, kp.DIM)).astype(np.float32))
    out = kp.pallas_rmw(table, idx, grads, interpret=True)
    ref = table.at[idx].add(grads)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_tile_ring_rmw_on_sorted_unique_ids(small_shapes, dtype):
    """The ring kernel keeps DEPTH tiles in flight, which only SORTED,
    UNIQUE ids make race-free: rows that share a tile are neighbours and
    no two groups of a chunk share one. Ids past the table (the empty
    slots ``row_runs`` puts behind the distinct rows) are skipped, and a
    tile split over two chunks is written twice, one chunk after the
    other. Both dtypes: a bfloat16 tile is 16 rows, two to a sublane."""
    import jax.numpy as jnp

    vocab, n = small_shapes
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.standard_normal((vocab, kp.DIM)), dtype)
    live = np.sort(rng.choice(vocab, 80, replace=False)).astype(np.int32)
    idx = np.concatenate([live, vocab + np.arange(80, n)]).astype(np.int32)
    grads = jnp.asarray(rng.standard_normal((n, kp.DIM)).astype(np.float32))
    want = (table.astype(jnp.float32).at[live].add(grads[:80])
            .astype(table.dtype))
    for kernel in (kp.pallas_rmw, kp.pallas_ring_rmw):
        out = kernel(table, jnp.asarray(idx), grads, interpret=True)
        assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                     - want.astype(jnp.float32)))) == 0.0


def test_probe_forms_agree_off_the_chip():
    """Every form of the price table that returns a table, at toy size on
    the CPU: the float32 sums are the plain scatter-add up to summation
    order, and XLA:CPU, which applies a scatter's updates one after
    another, makes the each-update-rounded form the plain scatter-add bit
    for bit in bfloat16 (on the TPU it is not: docs/W2V_KERNEL.md)."""
    import jax
    import jax.numpy as jnp

    V, D, n = 512, 24, 4096
    rng = np.random.default_rng(5)
    ids = jnp.asarray(rng.zipf(1.3, n) % V, jnp.int32)
    upd = jnp.asarray(rng.standard_normal((n, D)).astype(np.float32) * 1e-2)
    forms = kp.forms(V)
    run_of_slot, uids, written = kp.row_runs(ids, V)
    sums = forms["combine.scatter_by_run"][0](run_of_slot, upd)
    sums = jnp.pad(sums, ((0, uids.shape[0] - n), (0, 0)))
    args = {"ids": ids, "upd": upd, "uids": uids, "sums": sums,
            "written": written}
    for dtype, tol in (("float32", 1e-5), ("bfloat16", 0.0)):
        table = jnp.asarray(rng.standard_normal((V, D)), dtype)
        plain = forms["plain"][0](table, ids, upd).astype(jnp.float32)
        for name in ("whole", "write.chunked_add", "write.chunked_set",
                     "write.add", "write.add_unpromised",
                     "write.gather_add_set", "whole.each_rounded"):
            if dtype == "bfloat16" and name != "whole.each_rounded":
                continue        # summed first: another result by design
            fn, argnames, _ = forms[name]
            got = jax.jit(fn)(table, *(args[a] for a in argnames[1:]))
            gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - plain)))
            assert gap <= tol, (name, dtype, gap)


@pytest.mark.parametrize("case", ["all_equal", "zipf_repeats",
                                  "ids_past_the_table"])
def test_combine_rows_sums_each_row_once(case):
    """The priced candidate's first half: the distinct ids in front,
    sorted AND unique to the padded end, each with the sum of its
    updates (exact: multiples of 2**-6); slots past the table are
    offered nothing."""
    import jax
    import jax.numpy as jnp

    V, D, n = 96, 24, 4096          # 4096 = two write chunks
    rng = np.random.default_rng(6)
    ids = {"all_equal": np.full(n, 7),
           "zipf_repeats": rng.zipf(1.3, n) % V,
           "ids_past_the_table": rng.integers(0, 2 * V, n)}[case]
    upd = (rng.integers(-256, 256, (n, D)) / 64.0).astype(np.float32)

    uids, sums, written = jax.jit(kp.combine_rows, static_argnums=2)(
        jnp.asarray(ids, jnp.int32), jnp.asarray(upd), V)

    live = ids < V
    distinct = np.unique(ids[live])
    uids, sums, written = np.asarray(uids), np.asarray(sums), int(written)
    assert written == distinct.shape[0]
    assert uids.shape[0] % kp.WRITE_CHUNK_ROWS == 0 and uids.shape[0] >= n
    assert (np.diff(uids) > 0).all()
    np.testing.assert_array_equal(uids[:written], distinct)
    assert (uids[written:] >= V).all()
    want = np.zeros((V, D), np.float32)
    np.add.at(want, ids[live], upd[live])
    np.testing.assert_array_equal(sums[:written], want[distinct])
    assert not sums[written:].any()
