"""Off-chip check of the harness (CPU, no chip, no device number).

    JAX_PLATFORMS=cpu python3 benchmarks/selfcheck.py

* runs each driver end to end at a tiny size through the harness's own
  ``run_cell`` (its look for a chip skipped): set-up, window, release,
  the reference, the comparison, and ``correct`` true;
* checks the trace reduction on a small trace recorded on the v5e
  (``fixtures/trace_w2v_v5e.json``): busy union, window, per-program
  device time, leaf ops, idle gaps by host span -- and on a hand-made
  two-chip trace: nested ops counted once, the collective share and its
  exposed part;
* checks the work counts against numbers worked out by hand.

Whatever it prints is plumbing: nothing here is a device number.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

# one cell per driver, and the keys that shrink it to a toy
CELL_OF = {"w2v_train": "w2v_news3m_sgns", "lm_train": "lm_train_gpt2s_8x1024",
           "serve_closed": "serve_gpt2s_closed128"}
SHRINK = {
    "w2v_train": dict(vocab_size=6000, embedding_size=32,
                      batch_size_per_worker=256, shared_negatives=16,
                      neg_pool_size=1 << 14, corpus_words=60000,
                      steps_per_dispatch=3, sentence_words=50,
                      total_words=1e7),
    "lm_train": dict(vocab_size=503, n_embd=64, n_layer=2, n_head=4,
                     n_inner=128, n_positions=64, batch=4, seq=64,
                     device_batches=4, attention="reference",
                     # a toy's rounding is not the cell's: limits of its own
                     limits={"loss_gap": 3e-4, "first_grad_norm_gap": 6e-3,
                             "change_norm_gap": 0.012}),
    "serve_closed": dict(vocab_size=503, n_embd=64, n_layer=2, n_head=4,
                         n_inner=128, n_positions=64, dtype="float32",
                         slots=4, clients=4, max_prompt=48, max_new=16,
                         prompt_min=4, prompt_max=48, new_min=4, new_max=16,
                         prefill_token_budget=16, requests=64, length_cycle=4,
                         ramp_s=0.5, limits={"token_logit_gap": 1e-3,
                                             "short_answers": 0},
                         check_requests=8),
}


def near(a, b, rel=1e-9):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


def check_drivers() -> None:
    from benchmarks import harness

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    have = {w["name"] for w in bench["workloads"]}
    for driver, cell in CELL_OF.items():
        if cell not in have:
            print(f"selfcheck: driver {driver}: no cell in BENCHMARK.json, "
                  "skipped")
            continue
        line = harness.run_cell(cell, 7, 0.5, False, require_tpu=False,
                                shrink=SHRINK[driver], bench=bench)
        assert line["correct"], (driver, line["compared"])
        assert line["attempted"] > 0 and line["failed"] == 0, line
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
        print(f"selfcheck: driver {driver}: correct, "
              f"{line['attempted']} attempted, compared {line['compared']}")


def check_trace_reduction() -> None:
    from benchmarks import tracered

    with open(os.path.join(HERE, "fixtures", "trace_w2v_v5e.json")) as fh:
        rows = json.load(fh)["rows"]
    r = tracered.reduce_events(rows, 0.0)
    assert r["chips"] == 1
    assert near(r["window_s"], 0.122), r["window_s"]
    # one run of the fused program covers 0.12 s of the cut; the ops
    # inside it leave 0.1 ms of gaps
    assert near(r["programs"]["jit_fused"]["s"], 0.12, 1e-6)
    assert r["programs"]["jit_fused"]["runs"] == 1
    assert 0.1198 < r["busy_s"] < 0.12, r["busy_s"]
    assert r["busy_s"] <= r["window_s"]
    assert near(sum(r["ops"].values()), r["busy_s"], 1e-3)   # leaves only
    assert r["collective_s"] == 0.0
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert near(sum(gaps.values()), r["window_s"] - r["busy_s"], 1e-6)
    assert len(r["breakdown"]["device_ops"]) == 10

    # hand-made: two chips; a while covers two leaves; an all-reduce
    # half under a fusion, half exposed
    ms = 1e6
    rows = [["/host:CPU", "python3", "bench.window", 0.0, 100 * ms]]
    for chip in (0, 1):
        p = f"/device:TPU:{chip}"
        rows += [
            [p, "XLA Modules", "jit_step(1)", 10 * ms, 60 * ms],
            [p, "XLA Ops", "%while.1 = () while()", 10 * ms, 40 * ms],
            [p, "XLA Ops", "%fusion.1 = f32[8] fusion()", 10 * ms, 20 * ms],
            [p, "XLA Ops", "%fusion.2 = f32[8] fusion()", 30 * ms, 20 * ms],
            [p, "XLA Ops", "%all-reduce.3 = f32[8] all-reduce()", 40 * ms,
             20 * ms],
        ]
    r = tracered.reduce_events(rows, 0.0)
    assert r["chips"] == 2 and near(r["window_s"], 0.1)
    assert near(r["busy_s"], 0.05), r["busy_s"]          # 10..60 ms, a chip
    assert near(r["collective_s"], 0.02) \
        and near(r["collective_exposed_s"], 0.01), r
    assert near(r["programs"]["jit_step"]["s"], 0.12)    # both chips
    assert "while.1 () while()" not in r["ops"]          # not a leaf
    assert tracered.union_ns([(0, 2), (1, 3), (5, 6)]) == 4
    assert tracered.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    print("selfcheck: trace reduction: ok")


def check_work_counts() -> None:
    from benchmarks import work

    assert work.w2v_flops_per_pair(300, 5) == 10800
    # 2 x 65,536 rows + 1,024 x 5 shared negatives, read and written
    assert work.w2v_bytes_per_step(65536, 300, 5, 64, 2) \
        == 2 * (131072 + 5120) * 300 * 2
    n = work.lm_matmul_params(768, 12, 3072, 50257)
    assert n == 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 50257
    step = work.train_flops_per_step(768, 12, 3072, 50257, 8, 1024)
    assert near(step, 8192 * (6 * n + 3 * 4 * 512 * 768 * 12))
    assert 6.4e12 < step < 6.7e12                       # ISSUE: 6.54 TFLOP
    f, b = work.causal_attention_work(8, 12, 1024, 64, 2, False)
    assert f == 96 * 2 * 2 * (1024 * 1024 / 2) * 64 and b == 96 * 4 * 131072
    assert work.decode_step_bytes(768, 12, 3072, 50257, 1000.0, 2) \
        == 2 * (n + 2 * 12 * 768 * 1000.0)
    assert work.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    try:
        work.peaks("no such chip")
    except SystemExit:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")
    print("selfcheck: work counts: ok")


def main() -> int:
    check_work_counts()
    check_trace_reduction()
    check_drivers()
    print("selfcheck: all ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
