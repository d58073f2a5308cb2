"""LM training MFU on the real chip.

Measures the TransformerLM train step's DEVICE time via xprof (a host
clock around an async dispatch measures the enqueue — see
tools/xprof_util.py) and divides the step's matmul FLOPs by the chip's
bf16 peak to report MFU at seq 1024/2048 with reference vs flash
attention. The parent never touches JAX: each measurement is a child
that holds the chip alone.

FLOP accounting (causal-aware, so MFU is not inflated by counting work
the kernels skip):

* matmul params N = L*(4*d^2 + 2*d*d_ff) + d*vocab (the logits head;
  the embedding lookup is a gather, not a matmul);
* forward = 2*N FLOPs/token + attention 2*2*(T/2)*d per layer
  (QK^T and PV over an average causal span of T/2);
* training = 3x forward (bwd does ~2x fwd's matmul work).

Usage: python tools/lm_mfu.py [--out docs/LM_MFU.md] [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# bf16 peak FLOP/s per chip, keyed by ``jax.Device.device_kind``. A kind
# that is not here is an error, not a default.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # Google Cloud documentation, "TPU v5e"
}


def peak_flops(device_kind: str) -> float:
    try:
        return PEAK_FLOPS[device_kind]
    except KeyError:
        raise SystemExit(
            f"lm_mfu: no bf16 peak on record for device kind "
            f"{device_kind!r}; add it to PEAK_FLOPS with its source")


_VOCAB = 256


def train_flops_per_step(d_model: int, n_layers: int, d_ff: int,
                         vocab: int, batch: int, seq: int) -> float:
    n_matmul = n_layers * (4 * d_model * d_model + 2 * d_model * d_ff) \
        + d_model * vocab
    per_token = 6 * n_matmul + 3 * 4 * (seq / 2) * d_model * n_layers
    return per_token * batch * seq


def _measure_one(argv) -> None:
    """Subprocess entry: ONE xprof trace of the jitted train step."""
    import jax
    import jax.numpy as jnp

    import multiverso_tpu as mv
    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
    from tools.xprof_util import trace_device_ms

    mv.init(["lm_mfu", "-log_level=error"])
    d_model, n_layers, n_heads, d_ff, batch, seq, attn, dtype = argv
    cfg = TransformerConfig(
        vocab_size=_VOCAB, d_model=int(d_model), n_heads=int(n_heads),
        n_layers=int(n_layers), d_ff=int(d_ff), max_seq=int(seq),
        attention=attn,
        dtype=jnp.bfloat16 if dtype == "bf16" else jnp.float32)
    lm = TransformerLM(cfg)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, _VOCAB, (int(batch), int(seq))).astype(np.int32)
    float(lm.train_batch(toks))                   # compile + land
    ms = trace_device_ms(lambda: lm.train_batch(toks))
    print(f"DEVICE_KIND {jax.devices()[0].device_kind}")
    print(f"DEVICE_MS {ms:.6f}")


def measure(d_model, n_layers, n_heads, d_ff, batch, seq, attn, dtype):
    """(device ms per step, device kind) from one child process."""
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--_one",
         str(d_model), str(n_layers), str(n_heads), str(d_ff),
         str(batch), str(seq), attn, dtype],
        capture_output=True, text=True, timeout=900)
    kind = None
    for line in out.stdout.splitlines():
        if line.startswith("DEVICE_KIND "):
            kind = line[len("DEVICE_KIND "):]
        if line.startswith("DEVICE_MS ") and kind is not None:
            return float(line.split()[1]), kind
    raise RuntimeError(f"measure failed:\n{out.stdout[-2000:]}\n"
                       f"{out.stderr[-2000:]}")


def main(argv=None) -> int:
    if argv is None and len(sys.argv) >= 2 and sys.argv[1] == "--_one":
        _measure_one(sys.argv[2:])
        return 0
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)

    # flagship-ish size: 85M matmul params — big enough that the MXU, not
    # dispatch, is the limiter on one chip
    d_model, n_layers, n_heads = 768, 12, 12
    d_ff = 4 * d_model
    rows = []
    seqs = (1024,) if args.quick else (1024, 2048)
    for seq in seqs:
        batch = max(1, (8 * 1024) // seq)         # ~8k tokens/step
        for attn in ("reference", "flash"):
            for dtype in ("bf16",):
                ms, kind = measure(d_model, n_layers, n_heads, d_ff, batch,
                                   seq, attn, dtype)
                peak = peak_flops(kind)
                flops = train_flops_per_step(d_model, n_layers, d_ff,
                                             _VOCAB, batch, seq)
                mfu = flops / (ms / 1e3) / peak
                tok_s = batch * seq / (ms / 1e3)
                rows.append({"seq": seq, "batch": batch, "attention": attn,
                             "dtype": dtype, "device_kind": kind,
                             "step_ms": ms,
                             "tok_per_s": tok_s, "mfu": mfu})
                print(f"seq={seq} batch={batch} attn={attn} {dtype}: "
                      f"{ms:.2f} ms/step, {tok_s:,.0f} tok/s, "
                      f"MFU {mfu * 100:.1f}%", flush=True)

    if args.out:
        n_params = n_layers * (4 * d_model ** 2 + 2 * d_model * d_ff) \
            + d_model * _VOCAB
        lines = [
            f"# LM training MFU (one {kind} chip, device-time via xprof)",
            "",
            f"`tools/lm_mfu.py` — byte-level TransformerLM, d_model "
            f"{d_model}, {n_layers} layers, {n_heads} heads, d_ff {d_ff} "
            f"({n_params / 1e6:.0f}M matmul params), bf16 params, ~8k "
            "tokens/step. MFU = causal-aware matmul FLOPs / device time "
            f"/ {peak / 1e12:.0f} TFLOP/s ({kind} bf16 peak); the "
            "attention column is TransformerConfig.attention.",
            "",
            "| seq | batch | attention | step ms | tok/s | MFU |",
            "|---|---|---|---|---|---|",
        ]
        for r in rows:
            lines.append(
                f"| {r['seq']} | {r['batch']} | {r['attention']} "
                f"| {r['step_ms']:.2f} | {r['tok_per_s']:,.0f} "
                f"| {r['mfu'] * 100:.1f}% |")
        lines += [
            "",
            "The flash rows are exactly what `attention=\"flash\"` users "
            "get: `best_attention` with the batched crossover (seq 512 "
            "when B > 1 — measured in-model, where flash ties XLA at 512 "
            "and wins above; the standalone single-sequence crossover "
            "stays 1536, docs/TPU_VALIDATE.json — measured on an earlier "
            "device set-up; crossover to be re-measured by a benchmark "
            "PR). Layers are unrolled by "
            "default (`scan_layers=False`): the layer-stack `lax.scan` "
            "measured +27% device time at this shape (58.7 vs 46.2 "
            "ms/step, r5 re-probe) in scan-carry copies and grad-stack "
            "dynamic-update-slices. No remat: per-layer `jax.checkpoint` "
            "re-probed at +30% (60.1 ms/step) — activations fit HBM at "
            "this scale, so recompute buys nothing.",
            "",
            "r5 step anatomy (xprof per-op at seq 1024): param matmuls "
            "~26.5 ms (~80% of bf16 peak), attention is the rest. Four "
            "measured changes took the flash step 54.1 -> 46.2 ms/step "
            "(45.1% -> 51% MFU): full-length-forward loss (kills the "
            "seq-1023 pad/slice around every kernel, -1.4 ms), "
            "kernel-native bf16 output (-1 ms), a fused one-pass "
            "backward kernel for the one-k-block case (5 dots vs the "
            "two-pass 7, -3.6 ms), and a plain-softmax one-k-block "
            "forward kernel (no online-softmax carries, -1.1 ms). "
            "Measured rejections, same shape: finer block sizes "
            "(512/256 — causal-skip savings lose to grid overhead, "
            "tools/flash_block_probe.py), fused QKV concat gemm "
            "(-0.18 ms only), and the r4 `_pad_dim` question — "
            "lane-padded vs unpadded d=64 is a 0.27% wash in-model "
            "(53.94 vs 54.08 ms pre-fusion), so the r4 snapshot's '30% "
            "of the train step' padding attribution was wrong; the "
            "unpadded form stays for its halved VMEM footprint.",
            "",
        ]
        with open(args.out, "w") as f:
            f.write("\n".join(lines))
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
