"""Ops tests: embedding gather/scatter, ring attention vs oracle."""

import numpy as np
import pytest


def test_embedding_lookup_and_scatter():
    import jax.numpy as jnp
    from multiverso_tpu.ops import embedding_lookup, scatter_add_rows

    table = jnp.arange(20, dtype=jnp.float32).reshape(5, 4)
    rows = embedding_lookup(table, jnp.array([0, 3, 3]))
    np.testing.assert_allclose(np.asarray(rows)[1], np.arange(12, 16))
    updated = scatter_add_rows(table, jnp.array([1, 1]),
                               jnp.ones((2, 4), jnp.float32))
    np.testing.assert_allclose(np.asarray(updated)[1], np.arange(4, 8) + 2)


_N, _V, _D = 256, 96, 24


def _row_write_case(case, rng):
    """``(ids, number of table rows)``."""
    if case == "all_equal":
        return np.full(_N, 7), _V
    if case == "all_distinct":
        return rng.permutation(4 * _N)[:_N], 4 * _N
    if case == "zipf_repeats":
        p = 1.0 / np.arange(1, _V + 1)
        return rng.choice(_V, _N, p=p / p.sum()), _V
    assert case == "ids_past_the_table"     # dropped, as the trainer's
    ids = rng.integers(0, _V, _N)           # masked slots may be
    ids[::5] = _V + rng.integers(0, 1000, ids[::5].shape[0])
    return ids, _V


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", [
    "all_equal", "all_distinct", "zipf_repeats", "ids_past_the_table"])
def test_scatter_add_rows_against_numpy(case, dtype):
    """The word2vec step's table write against ``np.add.at``: exact
    here, where tables and updates are multiples of 2**-3 and a row's
    256 updates of -1/8, 0 or 1/8 never walk past what eight bits hold,
    so both dtypes keep every partial sum in any order."""
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.ops import scatter_add_rows

    rng = np.random.default_rng(3)
    ids, rows = _row_write_case(case, rng)
    table = rng.integers(-16, 16, (rows, _D)) / 8.0
    upd = (rng.integers(-1, 2, (_N, _D)) / 8.0).astype(np.float32)

    got = jax.jit(scatter_add_rows)(
        jnp.asarray(table, dtype), jnp.asarray(ids, jnp.int32),
        jnp.asarray(upd))

    assert got.dtype == jnp.dtype(dtype)
    live = ids < rows
    want = table.copy()
    np.add.at(want, ids[live], upd[live].astype(np.float64))
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)), want.astype(np.float32))


def test_a_bfloat16_table_keeps_every_updates_own_rounding():
    """Why the step's updates are NOT summed by row before the write: a
    bfloat16 row at 1.0 (ulp 2**-7) offered 256 updates of 2**-10 stays
    1.0 under the scatter-add, each update lost on its own, where their
    float32 sum carries it to 1.25. The benchmark's reference rounds and
    adds each update, and the trainer follows it (docs/W2V_KERNEL.md,
    4 October 2026: summed first is 19% faster and not ``correct``)."""
    import jax.numpy as jnp
    from multiverso_tpu.ops import scatter_add_rows

    table = jnp.ones((8, 4), jnp.bfloat16)
    ids = jnp.full((256,), 5, jnp.int32)
    upd = jnp.full((256, 4), 2.0 ** -10, jnp.float32)
    got = scatter_add_rows(table, ids, upd)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.ones((8, 4), np.float32))
    summed_first = table.at[5].add(upd.sum(axis=0).astype(table.dtype))
    assert float(summed_first[5, 0]) == 1.25


def test_segment_mean():
    import jax.numpy as jnp
    from multiverso_tpu.ops import segment_mean_rows

    vals = jnp.array([[2.0, 2.0], [4.0, 4.0], [10.0, 10.0]])
    out = segment_mean_rows(vals, jnp.array([0, 0, 1]), 2)
    np.testing.assert_allclose(np.asarray(out), [[3, 3], [10, 10]])


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_oracle(mv_session, causal):
    import jax.numpy as jnp
    import multiverso_tpu as mv
    from multiverso_tpu.ops import reference_attention, ring_attention
    from multiverso_tpu.topology import SEQ_AXIS, make_mesh

    mesh = make_mesh((4,), axis_names=(SEQ_AXIS,))
    rng = np.random.default_rng(0)
    seq, heads, dim = 32, 2, 8
    q = jnp.asarray(rng.standard_normal((seq, heads, dim)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((seq, heads, dim)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((seq, heads, dim)), jnp.float32)
    with_ring = np.asarray(ring_attention(q, k, v, mesh, causal=causal))
    oracle = np.asarray(reference_attention(q, k, v, causal=causal))
    np.testing.assert_allclose(with_ring, oracle, atol=2e-5, rtol=2e-5)


def test_ring_attention_differentiable(mv_session):
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.ops import ring_attention
    from multiverso_tpu.topology import SEQ_AXIS, make_mesh

    mesh = make_mesh((4,), axis_names=(SEQ_AXIS,))
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((16, 1, 4)), jnp.float32)

    def loss(q):
        out = ring_attention(q, q, q, mesh, causal=True)
        return jnp.sum(out ** 2)

    g = jax.grad(loss)(q)
    assert np.isfinite(np.asarray(g)).all()
    assert np.abs(np.asarray(g)).sum() > 0
