"""Transformer LM: dp+tp training on the virtual mesh, correctness vs
unsharded forward. (No reference counterpart — SURVEY §5.7 — this is the
framework's parallelism-showcase model family.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.transformer import (TransformerConfig,
                                               TransformerLM, forward,
                                               init_params, loss_fn,
                                               param_shardings)
from multiverso_tpu.topology import SERVER_AXIS, make_mesh


def _copy_task_batch(rng, batch, seq, vocab):
    """Sequences of the form [a b c a b c ...] — learnable structure."""
    period = 3
    base = rng.integers(1, vocab, (batch, period))
    reps = (seq + period - 1) // period
    return np.tile(base, (1, reps))[:, :seq].astype(np.int32)


def test_sharded_forward_matches_unsharded():
    cfg = TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                            n_layers=2, d_ff=32, max_seq=16)
    mesh = make_mesh((4, 2))
    params = init_params(cfg)
    tokens = np.arange(2 * 8).reshape(2, 8).astype(np.int32) % 32

    ref = np.asarray(forward(cfg, params, jnp.asarray(tokens)))

    sharded = jax.tree.map(jax.device_put, params,
                           param_shardings(cfg, mesh))
    out = np.asarray(
        jax.jit(lambda p, t: forward(cfg, p, t))(sharded,
                                                 jnp.asarray(tokens)))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_greedy_decode_eos_freezes_lanes():
    """eos_id freezes finished lanes: output prefixes (through the eos
    token) are bit-identical to the eos_id=None run, everything after is
    pad, and unfinished lanes are untouched end to end."""
    from multiverso_tpu.models.transformer import greedy_decode

    cfg = TransformerConfig(vocab_size=37, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=32)
    params = init_params(cfg)
    rng = np.random.default_rng(2)
    lengths = np.array([5, 2, 7, 1], np.int32)
    toks = np.zeros((4, 7), np.int32)
    for b, l in enumerate(lengths):
        toks[b, :l] = rng.integers(1, cfg.vocab_size, l)
    new = 12
    plain = np.asarray(greedy_decode(
        cfg, params, jnp.asarray(toks), jnp.asarray(lengths), new))
    # pick the most common generated token as eos so some lane freezes
    eos = int(np.bincount(plain.ravel()).argmax())
    froze = np.asarray(greedy_decode(
        cfg, params, jnp.asarray(toks), jnp.asarray(lengths), new, eos))
    assert froze.shape == plain.shape
    hit_any = False
    for b in range(4):
        hits = np.nonzero(plain[b] == eos)[0]
        if hits.size:
            hit_any = True
            cut = hits[0] + 1
            np.testing.assert_array_equal(froze[b, :cut], plain[b, :cut])
            assert (froze[b, cut:] == 0).all(), "frozen lane kept emitting"
        else:
            np.testing.assert_array_equal(froze[b], plain[b])
    assert hit_any, "no lane hit eos; test seed needs regenerating"


def test_training_decreases_loss(mv_session):
    cfg = TransformerConfig(vocab_size=16, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=16,
                            learning_rate=0.3)
    model = TransformerLM(cfg, mesh=make_mesh((4, 2)))
    rng = np.random.default_rng(0)
    first = last = None
    for i in range(40):
        batch = _copy_task_batch(rng, batch=8, seq=12, vocab=16)
        loss = float(model.train_batch(batch))
        if first is None:
            first = loss
        last = loss
    assert np.isfinite(last)
    assert last < first * 0.7, (first, last)


def test_param_shardings_cover_tree():
    cfg = TransformerConfig(vocab_size=8, d_model=8, n_heads=2, n_layers=1,
                            d_ff=16, max_seq=8)
    mesh = make_mesh((4, 2))
    params = init_params(cfg)
    shardings = param_shardings(cfg, mesh)
    assert (jax.tree.structure(params) == jax.tree.structure(shardings))
    spec = shardings["layers"]["w_q"].spec
    assert SERVER_AXIS in spec


def test_flash_attention_backend_trains(mv_session):
    """cfg.attention='flash' routes the LM through the Pallas kernel
    (interpret mode on CPU) including its custom-VJP backward."""
    import numpy as np

    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)

    mv = mv_session
    cfg = TransformerConfig(vocab_size=32, d_model=32, n_heads=2,
                            n_layers=1, d_ff=64, max_seq=16,
                            attention="flash")
    ref_cfg = TransformerConfig(vocab_size=32, d_model=32, n_heads=2,
                                n_layers=1, d_ff=64, max_seq=16)
    lm = TransformerLM(cfg, mesh=mv.session().mesh)
    ref = TransformerLM(ref_cfg, mesh=mv.session().mesh)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 32, (4, 12)).astype(np.int32)
    l_flash = float(lm.train_batch(toks))
    l_ref = float(ref.train_batch(toks))
    # same init/seed: the two backends must agree on the first step's loss
    assert abs(l_flash - l_ref) < 5e-2, (l_flash, l_ref)
    l2 = float(lm.train_batch(toks))
    assert l2 < l_flash   # the custom VJP actually descends


def test_lm_app_cli(mv_session, tmp_path, monkeypatch):
    """apps/lm end-to-end: byte-level LM trains, checkpoints, resumes,
    and samples, on the virtual mesh."""
    import numpy as np

    from multiverso_tpu.apps import lm as lm_app

    corpus = tmp_path / "text.txt"
    corpus.write_bytes((b"the quick brown fox jumps over the lazy dog. "
                        * 200))
    ckpt = str(tmp_path / "ck")
    args = ["-train_file", str(corpus), "-d_model", "32", "-n_layers", "1",
            "-n_heads", "2", "-seq", "32", "-batch", "8", "-steps", "6",
            "-lr", "0.3", "-ckpt", ckpt, "-ckpt_every", "3",
            "-log_every", "0", "-sample", "8"]
    assert lm_app.main(list(args)) == 0

    from multiverso_tpu.io import checkpoint

    assert checkpoint.list_steps(ckpt) == [3, 6]

    # resume leg: a fresh session restores step 6 and continues to 8
    from multiverso_tpu.runtime import Session

    Session._instance = None
    import multiverso_tpu as mv

    mv.set_flag("mesh_shape", "")
    args2 = ["-train_file", str(corpus), "-d_model", "32", "-n_layers", "1",
             "-n_heads", "2", "-seq", "32", "-batch", "8", "-steps", "9",
             "-lr", "0.3", "-ckpt", ckpt, "-ckpt_every", "3",
             "-log_every", "0"]
    assert lm_app.main(list(args2)) == 0
    # the resume actually started from step 6: only step 9 is NEW (a
    # fresh-start run would have retrained and re-saved steps 3 and 6
    # before reaching 9 — and saved them with fresh mtimes)
    assert checkpoint.list_steps(ckpt) == [3, 6, 9]
    import os as _os

    t6 = _os.path.getmtime(_os.path.join(ckpt, "step_6", "manifest.json"))
    t9 = _os.path.getmtime(_os.path.join(ckpt, "step_9", "manifest.json"))
    assert t6 < t9 and (t9 - t6) > 1.0   # step_6 untouched by run 2


# -- serving: the path of K and V from the paged pool to the attention --------
def _per_head_attention(q, k, v, n_heads, pos):
    """The plain form: per (example, head) one row of scores, float32."""
    B, D = q.shape
    T, dh = k.shape[1], D // n_heads
    qh = q.astype(jnp.float32).reshape(B, n_heads, dh)
    kh = k.astype(jnp.float32).reshape(B, T, n_heads, dh)
    vh = v.astype(jnp.float32).reshape(B, T, n_heads, dh)
    scores = (qh[:, None] * kh).sum(-1).transpose(0, 2, 1) / np.sqrt(dh)
    live = jnp.arange(T)[None, None, :] <= pos[:, None, None]
    probs = jax.nn.softmax(jnp.where(live, scores, -1e30), axis=-1)
    # the program rounds the probabilities to the cache's type: so here
    probs = probs.astype(v.dtype).astype(jnp.float32)
    out = (probs.transpose(0, 2, 1)[..., None] * vh).sum(1)
    return out.reshape(B, D)


@pytest.mark.parametrize("n_heads", [1, 4, 12])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_cached_attention_matches_per_head_reference(dtype, n_heads):
    from multiverso_tpu.models.transformer import _cached_attention

    B, T, dh = 5, 24, 8
    D = n_heads * dh
    rng = np.random.default_rng(n_heads)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
               .astype(dtype)
               for shape in ((B, D), (B, T, D), (B, T, D)))
    pos = jnp.asarray([0, T - 1, 7, 1, T - 2], jnp.int32)   # ragged
    got = _cached_attention(q, k, v, n_heads, pos)
    assert got.dtype == q.dtype and got.shape == (B, D)
    want = np.asarray(_per_head_attention(q, k, v, n_heads, pos))
    got = np.asarray(got.astype(jnp.float32))
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        # the rounding of one bfloat16 output: half a unit in its last
        # place, at most 2**-8 of the value (8 significant bits)
        np.testing.assert_array_less(
            np.abs(got - want), 2.0 ** -8 * np.abs(want) + 1e-6)
    # pos 0 attends the first entry alone
    np.testing.assert_allclose(
        got[0], np.asarray(v[0, 0].astype(jnp.float32)), rtol=1e-6)


@pytest.mark.parametrize("pool_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("table_shape", [(6, 5), (5,)])
def test_paged_view_equals_take_of_the_layer(table_shape, pool_dtype):
    from multiverso_tpu.models.transformer import _paged_view

    L, N, Bs, D = 3, 11, 4, 8
    rng = np.random.default_rng(len(table_shape))
    pool = jnp.asarray(rng.integers(-100, 100, (L, N, Bs, D)), pool_dtype)
    tables = rng.integers(0, N, table_shape).astype(np.int32)
    tables.flat[0], tables.flat[-1] = 0, N - 1   # scratch and last block
    tables = jnp.asarray(tables)
    for layer in range(L):
        view = _paged_view(pool, layer, tables)
        want = jnp.take(pool[layer], tables, axis=0).reshape(
            table_shape[:-1] + (table_shape[-1] * Bs, D))
        assert view.dtype == pool.dtype and view.shape == want.shape
        np.testing.assert_array_equal(np.asarray(view), np.asarray(want))
