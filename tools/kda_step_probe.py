"""On-chip probe of the KDA decode step's state update at the Ling
cell's shape: a pool of ``[6, 128, 32, 128, 128]`` float32 states
(1.61 GB), one layer moved a call.

Runs ``ops.kda.kda_step_pool`` (the Pallas kernel: one pass over a head's
state, in place in the pool) for every ``--heads`` a grid step, and what
it replaces (``ops.kda.kda_step`` on the layer's slab, written back with
the inactive slots kept: XLA reads the slab twice and writes it once),
with the pool donated as the serving step donates it, and prints, a
layer: device ms (profiler, ``tools/xprof_util``), the share of the HBM
roofline (the layer's states read once and written once, 537 MB, at 819
GB/s: 0.66 ms), and the widest gap between the two results. One process.

Usage: python tools/kda_step_probe.py [--heads 8,16,32]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

LAYERS, SLOTS, HEADS, DK, DV = 6, 128, 32, 128, 128
HBM_BYTES_PER_S = 819e9


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--heads", default="8,16,32")
    ap.add_argument("--iters", type=int, default=12)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from multiverso_tpu.ops import kda
    from tools.xprof_util import trace_device_ms

    if jax.default_backend() != "tpu":
        print("kda_step_probe: needs a TPU", file=sys.stderr)
        return 3
    rng = np.random.default_rng(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    shape = (SLOTS, HEADS, DK)
    q = jnp.asarray(unit(rng.standard_normal(shape)) * DK ** -0.5,
                    jnp.float32)
    k = jnp.asarray(unit(rng.standard_normal(shape)), jnp.float32)
    v = jnp.asarray(0.6 * rng.standard_normal((SLOTS, HEADS, DV)),
                    jnp.float32)
    log_a = jnp.asarray(-5 / (1 + np.exp(5.5 - 1.4 * rng.standard_normal(
        shape))), jnp.float32)
    b = jnp.asarray(rng.uniform(0.1, 0.9, (SLOTS, HEADS)), jnp.float32)
    active = jnp.asarray(np.arange(SLOTS) != 7)
    draw = jax.jit(lambda key: 0.05 * jax.random.normal(
        key, (LAYERS, SLOTS, HEADS, DK, DV), jnp.float32))
    least_ms = 1e3 * 2 * SLOTS * HEADS * DK * DV * 4 / HBM_BYTES_PER_S

    @functools.partial(jax.jit, donate_argnums=(0,))
    def slab(pool):
        o, new = kda.kda_step(q, k, v, log_a, b, pool[2])
        return pool.at[2].set(jnp.where(active[:, None, None, None], new,
                                        pool[2])), o

    pool, want_o = slab(draw(jax.random.PRNGKey(1)))
    want = np.asarray(pool[2, :8])
    rows = {}

    def timed(fn, pool):
        def run():
            nonlocal pool
            pool, o = fn(pool)
            return o
        run()
        return trace_device_ms(run, args.iters), pool

    ms, pool = timed(slab, pool)
    rows["xla_slab"] = {"ms": ms, "roofline_pct": 100 * least_ms / ms}
    del pool
    for hb in (int(h) for h in args.heads.split(",")):
        kda._STEP_HEADS = hb
        kda._kda_step_pool.clear_cache()

        @functools.partial(jax.jit, donate_argnums=(0,))
        def kernel(pool):
            o, pool = kda.kda_step_pool(q, k, v, log_a, b, active, pool, 2)
            return pool, o

        try:
            pool, o = kernel(draw(jax.random.PRNGKey(1)))
            gap = float(np.abs(np.asarray(pool[2, :8]) - want).max())
            # a slot that is not active is a dead lane: its output
            # means nothing in either form
            o_gap = float(jnp.abs(jnp.where(active[:, None, None],
                                            o - want_o, 0.0)).max())
            ms, pool = timed(kernel, pool)
            rows[f"kernel_heads_{hb}"] = {
                "ms": ms, "roofline_pct": 100 * least_ms / ms,
                "state_gap": gap, "o_gap": o_gap}
            del pool
        except Exception as exc:            # a block the chip refuses
            rows[f"kernel_heads_{hb}"] = {"error": str(exc)[:300]}
    print(json.dumps({"least_ms": least_ms, "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
