"""On-chip block-size probe for the flash kernel at the flagship LM
attention shape (r5).

At seq 1024 the default 1024x1024 blocks make the causal kernel compute
the FULL score matrix (one k-block -> nothing to skip), so ~2x the
needed work; finer blocks let the `run` predicate skip above-diagonal
blocks at the cost of more grid steps. This probe measures the real
trade on hardware: vmapped (B=8) fwd+bwd at [B, seq, 12 heads, 64 dim]
— exactly the tools/lm_mfu.py in-model attention call — for a sweep of
(block_q, block_k). One child process per point, timed by its device
trace; the parent stays off JAX so that each child can hold the chip.

Usage: python tools/flash_block_probe.py [--seq 1024]
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _one(seq: int, bq: int, bk: int) -> None:
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.ops.flash_attention import flash_attention
    from tools.xprof_util import trace_device_ms

    B, h, d = 8, 12, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, seq, h, d)), jnp.bfloat16)

    def loss(q, k, v):
        out = jax.vmap(lambda a, b, c: flash_attention(
            a, b, c, causal=True, block_q=bq, block_k=bk))(q, k, v)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    jax.block_until_ready(fn(q, q, q))
    ms = trace_device_ms(lambda: fn(q, q, q))
    print(f"DEVICE_MS {ms:.6f}")


def main(argv=None) -> int:
    if argv is None and len(sys.argv) >= 2 and sys.argv[1] == "--_one":
        _one(int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
        return 0
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=1024)
    args = ap.parse_args(argv)

    for bq, bk in ((1024, 1024), (512, 1024), (512, 512), (256, 512),
                   (256, 256), (128, 256)):
        if bq > args.seq or bk > args.seq:
            continue
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--_one",
             str(args.seq), str(bq), str(bk)],
            capture_output=True, text=True, timeout=600)
        ms = None
        for line in out.stdout.splitlines():
            if line.startswith("DEVICE_MS "):
                ms = float(line.split()[1])
        if ms is None:
            print(f"bq={bq} bk={bk}: FAILED\n{out.stdout[-800:]}"
                  f"{out.stderr[-800:]}")
            continue
        print(f"seq={args.seq} bq={bq} bk={bk}: {ms:.3f} ms "
              f"(B=8, h=12, d=64, fwd+bwd, device)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
