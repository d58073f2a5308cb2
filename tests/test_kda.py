"""``ops/kda.py`` against the recurrence it states, on the CPU in float32:
the chunkwise form (blocks, the WY system, the state carried between
blocks) equals the token-by-token gated delta rule for lengths that are
and are not multiples of the block, from a non-zero start state, with
padded rows, with every gate AT its lower bound through a whole chunk
(where a split exponent overflows float32), and with the write strength
at 0 and at 1; the one-token step is one step of the recurrence, and so
is the Pallas kernel that moves a layer's states in place in the pool
(interpreted here; compiled for the chip in ``test_tpu_compile.py``);
and the convolution continued over a chunk boundary from its tail is the
convolution of the whole."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from multiverso_tpu.ops import kda

H, DK, DV = 3, 16, 8
# float32 sums of a few hundred terms of size <= 1, in two orders
TOL = 2e-5


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def recurrence(q, k, v, log_a, b, state):
    """The module docstring's three lines, a token at a time."""
    def token(S, x):
        q, k, v, g, b = x
        S = S * jnp.exp(g)[..., None]
        u = v - jnp.einsum("hc,hcv->hv", k, S)
        S = S + (b[:, None] * k)[..., None] * u[:, None, :]
        return S, jnp.einsum("hc,hcv->hv", q, S)

    state, o = jax.lax.scan(token, state, (q, k, v, log_a, b))
    return o, state


def inputs(seed, T, gate="law", beta="law", start=True):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.standard_normal((T, H, DK))) * DK ** -0.5
    k = unit(rng.standard_normal((T, H, DK)))
    v = rng.standard_normal((T, H, DV))
    log_a = {"law": -5 * rng.uniform(0, 0.2, (T, H, DK)),
             "bound": np.full((T, H, DK), -5.0),
             "none": np.zeros((T, H, DK))}[gate]
    b = {"law": rng.uniform(0, 1, (T, H)), "zero": np.zeros((T, H)),
         "one": np.ones((T, H))}[beta]
    S = rng.standard_normal((H, DK, DV)) if start else np.zeros((H, DK, DV))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, log_a, b, S))


@pytest.mark.parametrize("T", [1, 5, 64, 100, 128, 200])
@pytest.mark.parametrize("start", [True, False], ids=["carried", "zero"])
def test_chunkwise_equals_the_recurrence(T, start):
    x = inputs(T, T, start=start)
    want_o, want_s = recurrence(*x)
    got_o, got_s = jax.jit(kda.kda_chunk)(*x)
    np.testing.assert_allclose(got_o, want_o, atol=TOL)
    np.testing.assert_allclose(got_s, want_s, atol=TOL)
    assert float(jnp.abs(want_o).max()) > 0.05      # a live layer


@pytest.mark.parametrize("gate,beta", [
    ("bound", "law"), ("bound", "one"), ("none", "one"), ("law", "zero"),
    ("law", "one"), ("none", "zero")])
def test_chunkwise_at_the_gates_ends(gate, beta):
    """Every gate at -5 through 150 positions: ``e^(-G_j)`` of a block
    of 64 would be ``e^320``; the exponent is formed whole, for ``t >=
    j`` only, so nothing overflows. ``b = 0`` leaves a decaying state,
    ``b = 1`` with no decay is the plain delta rule."""
    x = inputs(7, 150, gate=gate, beta=beta)
    want_o, want_s = recurrence(*x)
    got_o, got_s = jax.jit(kda.kda_chunk)(*x)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=TOL)
    np.testing.assert_allclose(got_s, want_s, atol=TOL)


@pytest.mark.parametrize("block", [16, 64])
def test_padded_rows_are_the_identity_on_the_state(block):
    """A chunk of 96 rows that holds 41 tokens: the state is the one
    after 41 tokens, whatever the padded rows hold."""
    q, k, v, log_a, b, S = inputs(3, 96)
    valid = jnp.arange(96) < 41
    want_o, want_s = recurrence(q[:41], k[:41], v[:41], log_a[:41], b[:41],
                                S)
    got_o, got_s = kda.kda_chunk(q, k, v, log_a, b, S, valid, block=block)
    np.testing.assert_allclose(got_o[:41], want_o, atol=TOL)
    np.testing.assert_allclose(got_s, want_s, atol=TOL)


def test_two_chunks_carry_the_state():
    x = inputs(5, 160)
    want_o, want_s = recurrence(*x)
    cut = lambda lo, hi: tuple(a[lo:hi] for a in x[:5])
    o1, s1 = kda.kda_chunk(*cut(0, 96), x[5])
    o2, s2 = kda.kda_chunk(*cut(96, 160), s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2]), want_o, atol=TOL)
    np.testing.assert_allclose(s2, want_s, atol=TOL)


def test_step_is_one_step_of_the_recurrence():
    slots = 5
    per_slot = [inputs(20 + s, 1) for s in range(slots)]
    stack = lambda i: jnp.stack([x[i][0] if i < 5 else x[i]
                                 for x in per_slot])
    got_o, got_s = jax.jit(kda.kda_step)(*(stack(i) for i in range(6)))
    for s, x in enumerate(per_slot):
        want_o, want_s = recurrence(*x)
        np.testing.assert_allclose(got_o[s], want_o[0], atol=TOL)
        np.testing.assert_allclose(got_s[s], want_s, atol=TOL)


def test_step_with_no_write_and_no_decay_keeps_the_state_bit_for_bit():
    q, k, v, _, _, S = inputs(9, 1)
    _, new = kda.kda_step(q, k, v, jnp.zeros((1, H, DK)), jnp.zeros((1, H)),
                          S[None])
    assert np.array_equal(np.asarray(new[0]), np.asarray(S))


@pytest.mark.parametrize("heads,dk,dv", [(4, 16, 8), (32, 128, 128)],
                         ids=["toy", "cell"])
def test_pool_step_kernel_is_the_step_in_place(heads, dk, dv):
    """``kda_step_pool`` (interpreted) on layer 1 of a three-layer pool:
    the live slots' states and outputs are ``kda_step``'s, a slot that
    is not active keeps its state bit for bit, and no other layer's
    state is touched."""
    rng = np.random.default_rng(4)
    slots = 3
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = (unit(rng.standard_normal((slots, heads, dk))) * dk ** -0.5) \
        .astype(np.float32)
    k = unit(rng.standard_normal((slots, heads, dk))).astype(np.float32)
    v = rng.standard_normal((slots, heads, dv)).astype(np.float32)
    log_a = (-5 * rng.uniform(0, 0.2, (slots, heads, dk))).astype(np.float32)
    b = rng.uniform(0, 1, (slots, heads)).astype(np.float32)
    pool = rng.standard_normal((3, slots, heads, dk, dv)).astype(np.float32)
    active = np.array([True, False, True])
    o, new = kda.kda_step_pool(q, k, v, log_a, b, active, jnp.asarray(pool),
                               1, interpret=True)
    want_o, want_s = kda.kda_step(q, k, v, log_a, b, pool[1])
    new = np.asarray(new)
    np.testing.assert_allclose(np.asarray(o)[active],
                               np.asarray(want_o)[active], atol=TOL)
    np.testing.assert_allclose(new[1][active], np.asarray(want_s)[active],
                               atol=TOL)
    assert np.array_equal(new[1][1], pool[1][1])
    assert np.array_equal(new[0], pool[0]) and np.array_equal(new[2], pool[2])


def test_pool_step_is_offered_only_where_the_kernel_applies(monkeypatch):
    assert kda.pool_step(32, 128, 128) is None          # the CPU
    monkeypatch.setattr(kda, "_on_tpu", lambda: True)
    assert kda.pool_step(32, 128, 128) is kda.kda_step_pool
    assert kda.pool_step(3, 16, 16) is None             # not whole tiles


def _conv_whole(x, taps):
    K = taps.shape[0]
    padded = np.concatenate([np.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(taps[j] * padded[j:j + len(x)] for j in range(K))


@pytest.mark.parametrize("cuts", [(40,), (3, 17, 18, 40), (1, 2, 3, 4, 5)])
def test_convolution_continues_over_chunk_boundaries(cuts):
    rng = np.random.default_rng(1)
    T, C, K = 40, 12, 4
    x = rng.standard_normal((T, C)).astype(np.float32)
    taps = rng.standard_normal((K, C)).astype(np.float32)
    want = _conv_whole(x, taps)
    tail = jnp.zeros((K - 1, C), jnp.float32)
    got, lo = [], 0
    for hi in cuts:
        # a chunk of 16 rows or more that holds hi - lo of them
        rows = np.zeros((max(hi - lo, 16), C), np.float32)
        rows[:hi - lo] = x[lo:hi]
        rows[hi - lo:] = 99.0                        # padding, never read
        y, tail = kda.short_conv_chunk(jnp.asarray(rows), jnp.asarray(taps),
                                       tail, hi - lo)
        got.append(np.asarray(y)[:hi - lo])
        lo = hi
    np.testing.assert_allclose(np.concatenate(got), want[:lo], atol=1e-5)
    # and one token at a time from where the chunks stopped
    if lo < T:
        tails = tail[None]
        for t in range(lo, T):
            y, tails = kda.short_conv_step(jnp.asarray(x[t])[None],
                                           jnp.asarray(taps), tails)
            np.testing.assert_allclose(y[0], want[t], atol=1e-5)
