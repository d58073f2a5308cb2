"""What ``correct`` has to catch, at a size a test run can hold (CPU).

Two kinds of test, for each driver:

* the control: the plain reference put in the program's place and
  computed in the nearest precision below the one the configuration
  states (an 8-bit float for bfloat16) has to come out as not correct;
* the faults: the harness's own run (its look for a chip skipped), with
  the timed path broken underneath the program, has to print
  ``correct: false`` -- a step that returns its state unchanged, half of
  the batch left out with the mean taken over the rest, a served token
  altered where it is produced.

The limits the harness applies are those of the cell's traffic file,
set on the chip at the cell's own size (PERF.md section 2); here they
only have to separate a sound toy run (which passes) from a broken one.

Run: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``
"""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402
from benchmarks.selfcheck import CELL_OF, SHRINK  # noqa: E402


def run(driver: str, seed: int = 11):
    return harness.run_cell(CELL_OF[driver], seed, 0.3, False,
                            require_tpu=False, shrink=SHRINK[driver])


def failed(line) -> list:
    return [n for n, (v, lim) in line["compared"].items()
            if v is None or v > lim]


# -- word2vec ---------------------------------------------------------------------
def test_w2v_sound_run_is_correct():
    line = run("w2v_train")
    assert line["correct"], line["compared"]


def _w2v_control(store="", fault=""):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx = harness.Ctx(bench, harness.find(bench["workloads"],
                                          CELL_OF["w2v_train"], "workload"),
                      11, 1, False, SHRINK["w2v_train"])
    ref = harness.load_module("reference", ctx.config_name)
    drv = harness.load_module("drivers", "w2v_train")
    sound = ref.follow(ctx.config, ctx.seed, 3)
    broken = ref.follow(ctx.config, ctx.seed, 3, store=store,
                        fault=fault)
    return drv.compare(broken, sound, ctx.traffic["limits"])


def test_w2v_control_fp8_tables_is_not_correct():
    rows = _w2v_control(store="float8_e5m2")
    assert any(r["value"] is None or r["value"] > r["limit"] for r in rows), rows


def test_w2v_reference_half_batch_is_not_correct():
    rows = _w2v_control(fault="half_batch")
    assert any(r["value"] is None or r["value"] > r["limit"] for r in rows), rows


def test_w2v_state_unchanged_is_not_correct(monkeypatch):
    from multiverso_tpu.models.word2vec import Word2Vec

    real = Word2Vec.train_device_steps

    def unchanged(self, n_steps):
        import jax.numpy as jnp

        keep = (jnp.copy(self.input_table._data),
                jnp.copy(self.output_table._data))
        out = real(self, n_steps)
        self.input_table._data, self.output_table._data = keep
        return out

    monkeypatch.setattr(Word2Vec, "train_device_steps", unchanged)
    line = run("w2v_train")
    assert not line["correct"] and failed(line), line["compared"]


def test_w2v_half_batch_is_not_correct(monkeypatch):
    from multiverso_tpu.models.word2vec import Word2Vec

    real = Word2Vec._build_step

    def build_step(self):
        jitted = real(self)
        step = self._raw_step

        def half(w_in, w_out, g_in, g_out, centers, contexts, mask, *rest):
            import jax.numpy as jnp

            n = mask.shape[0]
            return step(w_in, w_out, g_in, g_out, centers, contexts,
                        mask * (jnp.arange(n) < n // 2), *rest)

        self._raw_step = half
        return jitted

    monkeypatch.setattr(Word2Vec, "_build_step", build_step)
    line = run("w2v_train")
    assert not line["correct"] and failed(line), line["compared"]


# -- LM training --------------------------------------------------------------------
def test_lm_sound_run_is_correct():
    line = run("lm_train")
    assert line["correct"], line["compared"]


def _lm_control(compute="", fault=""):
    from benchmarks import gen

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx = harness.Ctx(bench, harness.find(bench["workloads"],
                                          CELL_OF["lm_train"], "workload"),
                      11, 1, False, SHRINK["lm_train"])
    ref = harness.load_module("reference", ctx.config_name)
    drv = harness.load_module("drivers", "lm_train")
    t = ctx.traffic
    batches = gen.lm_batches(ctx.seed, t["device_batches"], t["batch"],
                             t["seq"], ctx.config["vocab_size"])
    sound = ref.follow(ctx.config, ctx.seed31, batches, 3)
    broken = ref.follow(ctx.config, ctx.seed31, batches, 3, compute=compute,
                        fault=fault)
    return drv.compare(broken, sound, t["limits"])


def test_lm_control_fp8_matmuls_is_not_correct():
    rows = _lm_control(compute="float8_e4m3fn")
    assert any(r["value"] is None or r["value"] > r["limit"] for r in rows), rows


def test_lm_reference_half_batch_is_not_correct():
    rows = _lm_control(fault="half_batch")
    assert any(r["value"] is None or r["value"] > r["limit"] for r in rows), rows


def test_lm_state_unchanged_is_not_correct(monkeypatch):
    from multiverso_tpu.models.transformer import TransformerLM

    real = TransformerLM.train_batch

    def unchanged(self, tokens):
        import jax
        import jax.numpy as jnp

        keep = jax.tree.map(jnp.copy, (self.params, self._momentum))
        loss = real(self, tokens)
        self.params, self._momentum = keep
        return loss

    monkeypatch.setattr(TransformerLM, "train_batch", unchanged)
    line = run("lm_train")
    assert not line["correct"] and failed(line), line["compared"]


def test_lm_half_batch_is_not_correct(monkeypatch):
    from multiverso_tpu.models.transformer import TransformerLM

    real = TransformerLM.train_batch

    def half(self, tokens):
        import jax.numpy as jnp

        tokens = jnp.asarray(tokens)
        n = tokens.shape[0] // 2
        # the step keeps its shape: the second half repeats the first,
        # so the mean is the mean over the half that is left
        return real(self, jnp.concatenate([tokens[:n], tokens[:n]]))

    monkeypatch.setattr(TransformerLM, "train_batch", half)
    line = run("lm_train")
    assert not line["correct"] and failed(line), line["compared"]


# -- serving -------------------------------------------------------------------------
def test_serve_sound_run_is_correct():
    line = run("serve_closed")
    assert line["correct"], line["compared"]


def _serve_gaps(compute=""):
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx = harness.Ctx(bench, harness.find(bench["workloads"],
                                          CELL_OF["serve_closed"], "workload"),
                      11, 1, False, SHRINK["serve_closed"])
    ref = harness.load_module("reference", ctx.config_name)
    rng = np.random.default_rng(3)
    T = ctx.config["n_positions"]
    seqs = [rng.integers(0, ctx.config["vocab_size"], T).astype(np.int32)
            for _ in range(4)]
    return ref.token_gaps(ctx.config, ctx.seed31, seqs, [T // 2] * 4,
                          compute=compute)


def test_serve_control_fp8_picks_other_tokens():
    # the control need not decode: at each position of the same prompts
    # and tokens, the gap of the token the lower precision puts first
    assert max(_serve_gaps(compute="float8_e4m3fn")) > 0.0


def test_serve_altered_token_is_not_correct(monkeypatch):
    from concurrent.futures import Future

    from multiverso_tpu.serving.decode_engine import DecodeEngine

    real = DecodeEngine.submit

    def submit(self, prompt, *args, **kwargs):
        inner, outer = real(self, prompt, *args, **kwargs), Future()

        def relay(f):
            if f.exception() is not None:
                return outer.set_exception(f.exception())
            reply = dict(f.result())
            tokens = np.array(reply["result"])
            tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % 503
            reply["result"] = tokens
            outer.set_result(reply)

        inner.add_done_callback(relay)
        return outer

    monkeypatch.setattr(DecodeEngine, "submit", submit)
    line = run("serve_closed")
    assert not line["correct"] and failed(line), line["compared"]


def test_serve_short_answer_is_not_correct(monkeypatch):
    from concurrent.futures import Future

    from multiverso_tpu.serving.decode_engine import DecodeEngine

    real = DecodeEngine.submit

    def submit(self, prompt, *args, **kwargs):
        inner, outer = real(self, prompt, *args, **kwargs), Future()

        def relay(f):
            reply = dict(f.result())
            reply["result"] = np.asarray(reply["result"])[:-1]
            outer.set_result(reply)

        inner.add_done_callback(relay)
        return outer

    monkeypatch.setattr(DecodeEngine, "submit", submit)
    line = run("serve_closed")
    assert not line["correct"] and "short_answers" in failed(line)
