"""Measured probe of the w2v fused-kernel refutation (docs/W2V_KERNEL.md).

VERDICT r3 item 3 resolved as a written-up refutation whose load-bearing
claim — a Pallas per-row DMA kernel cannot beat the ~18 ns/row the XLA
scatter already sustains — was first argued from hardware constants.
This tool turns the argument
into on-chip numbers, and the first finding is stronger than the
argument: **the per-row DMA kernel class does not even compile.**
Mosaic rejects any HBM slice smaller than the hardware tile — dim-0
slices must be 8-aligned f32 (16 bf16), and a flat 1-D view must slice
in 1024-element units — so the minimum addressable DMA from a f32
table is the enclosing (8, D) tile. A "per-row" kernel is therefore
really a per-TILE kernel: 8x read amplification on the gather side and
8x+8x read+write on the RMW side, before any issue-rate argument.

What this probe measures on the real chip (same shape, same zipf index
distribution as the bench step):

  xla_scatter   table.at[idx].add(grads)     — the incumbent update op
  xla_gather    jnp.take(table, idx, 0)      — the incumbent gather
  pallas_gather per-row gather via enclosing-tile DMA, DEPTH=8
                ring-pipelined — the best per-row rate the kernel class
                reaches on its gather side alone (8 KB moved per row)
  pallas_rmw    per-row read-modify-write via enclosing-tile DMA,
                serial — what zipf duplicate rows allow (any pipelined
                RMW races whenever two in-flight rows share a tile,
                and the hottest zipf rows collide thousands of times
                per batch; 16 KB moved per row + 2 DMA waits)

Shape: D=256 f32 rows (1 KB; the bench's 200-dim rows are 800 B f32 /
400 B bf16 — the tile-granularity penalty this probe isolates only
grows as rows shrink relative to the fixed (8,128) tile), N = 204800
scattered rows into a 71296-row table, indices drawn zipf(1.0) like
the corpus. Timing is hardware ``device_duration_ps`` via
tools/xprof_util.py, one measurement per child process; the parent
stays off JAX so that each child can hold the chip.

Correctness is asserted before timing: the Pallas gather must equal
jnp.take exactly, and the serial RMW must equal scatter-add INCLUDING
duplicate rows.

Usage: python tools/w2v_kernel_probe.py [--json]
Reference metric under test: words/sec
(/root/reference/Applications/WordEmbedding/src/trainer.cpp:45-48).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

VOCAB = 71296
DIM = 256
N_ROWS = 204800
CHUNK = 2048          # rows per grid step (idx block = 8 KB SMEM)
DEPTH = 8             # in-flight DMA ring for the pipelined gather
TILE = 8              # f32 dim-0 tiling: the minimum HBM slice height


def _make_inputs():
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    # zipf-law draws like the corpus: duplicates are the NORM — the
    # hottest rows collect thousands of colliding updates
    ranks = np.arange(1, VOCAB + 1)
    p = 1.0 / ranks
    p /= p.sum()
    idx = rng.choice(VOCAB, size=N_ROWS, p=p)
    table = rng.standard_normal((VOCAB, DIM)).astype(np.float32)
    grads = (rng.standard_normal((N_ROWS, DIM)) * 1e-3).astype(np.float32)
    return (jnp.asarray(table), jnp.asarray(idx.astype(np.int32)),
            jnp.asarray(grads))


# ---------------------------------------------------------------- kernels


def _tile_slice(pl, idx):
    """The enclosing TILE-row slice of ``idx`` — the smallest HBM window
    Mosaic will DMA (sub-tile slices fail to compile; measured, see
    module docstring)."""
    return pl.ds(pl.multiple_of((idx // TILE) * TILE, TILE), TILE)


def _gather_kernel(idx_ref, table_ref, out_ref, scratch, sems):
    """Per-row gather via enclosing-tile DMA, DEPTH-deep ring."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def dma(i, slot):
        return pltpu.make_async_copy(
            table_ref.at[_tile_slice(pl, idx_ref[i]), :],
            scratch.at[pl.ds(slot * TILE, TILE), :],
            sems.at[slot])

    def retire(j, slot):
        dma(j, slot).wait()
        out_ref[pl.ds(j, 1), :] = scratch[
            pl.ds(slot * TILE + idx_ref[j] % TILE, 1), :]

    def body(i, _):
        slot = jax.lax.rem(i, DEPTH)

        @pl.when(i >= DEPTH)
        def _():
            retire(i - DEPTH, slot)

        dma(i, slot).start()
        return 0

    jax.lax.fori_loop(0, CHUNK, body, 0)

    def drain(k, _):
        j = CHUNK - DEPTH + k
        retire(j, jax.lax.rem(j, DEPTH))
        return 0

    jax.lax.fori_loop(0, DEPTH, drain, 0)


def pallas_gather(table, idx, interpret: bool = False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = idx.shape[0]
    return pl.pallas_call(
        _gather_kernel,
        grid=n // CHUNK,
        in_specs=[
            pl.BlockSpec((CHUNK,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((CHUNK, DIM), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, DIM), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((DEPTH * TILE, DIM), jnp.float32),
            pltpu.SemaphoreType.DMA((DEPTH,)),
        ],
        interpret=interpret,
    )(idx, table)


def _rmw_kernel(idx_ref, grad_ref, table_in_ref, table_out_ref,
                scratch, sem_in, sem_out):
    """Serial per-row read-modify-write via enclosing-tile DMA. Serial
    because zipf duplicates make any pipelined RMW racy: row i's tile
    write-back must land before a colliding row j>i reads the same
    tile — and collisions are the workload, not a corner case.

    Reads AND writes go through ``table_out_ref``: on TPU the aliased
    input is the same buffer, but interpret mode gives the input ref a
    stale snapshot — reading it would lose earlier duplicate-row
    updates (caught by tests/test_kernel_probe.py)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del table_in_ref     # aliased to table_out_ref; RMW uses one view

    def body(i, _):
        idx = idx_ref[i]
        tile = _tile_slice(pl, idx)
        pltpu.make_async_copy(table_out_ref.at[tile, :], scratch,
                              sem_in).start()
        pltpu.make_async_copy(table_out_ref.at[tile, :], scratch,
                              sem_in).wait()
        row = pl.ds(idx % TILE, 1)
        scratch[row, :] = scratch[row, :] + grad_ref[pl.ds(i, 1), :]
        pltpu.make_async_copy(scratch, table_out_ref.at[tile, :],
                              sem_out).start()
        pltpu.make_async_copy(scratch, table_out_ref.at[tile, :],
                              sem_out).wait()
        return 0

    jax.lax.fori_loop(0, CHUNK, body, 0)


def pallas_rmw(table, idx, grads, interpret: bool = False):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = idx.shape[0]
    return pl.pallas_call(
        _rmw_kernel,
        grid=n // CHUNK,
        in_specs=[
            pl.BlockSpec((CHUNK,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((CHUNK, DIM), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((table.shape[0], DIM), jnp.float32),
        input_output_aliases={2: 0},
        scratch_shapes=[
            pltpu.VMEM((TILE, DIM), jnp.float32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=interpret,
    )(idx, grads, table)


def subtile_rejected() -> str:
    """Self-verifying form of the probe's strongest finding: attempt the
    ACTUAL per-row kernel — a (1, DIM) HBM row slice DMA — and return
    the compiler's rejection. If a future Mosaic release starts
    accepting sub-tile slices, this raises and the 8x-amplification
    argument in docs/W2V_KERNEL.md must be re-measured."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kern(idx_ref, table_ref, out_ref, scratch, sem):
        def body(i, _):
            row = pl.ds(idx_ref[i], 1)           # sub-tile: 1 of 8 rows
            pltpu.make_async_copy(table_ref.at[row, :], scratch,
                                  sem).start()
            pltpu.make_async_copy(table_ref.at[row, :], scratch,
                                  sem).wait()
            out_ref[pl.ds(i, 1), :] = scratch[:, :]
            return 0

        jax.lax.fori_loop(0, 8, body, 0)

    call = pl.pallas_call(
        kern, grid=1,
        in_specs=[pl.BlockSpec((8,), lambda i: (0,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((8, DIM), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, DIM), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, DIM), jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    try:
        np.asarray(call(jnp.zeros(8, jnp.int32),
                        jnp.zeros((64, DIM), jnp.float32)))
    except Exception as exc:                     # expected: Mosaic reject
        # a rejection with ANY wording keeps the measured verdict valid;
        # only genuine ACCEPTANCE (the fall-through below) triggers the
        # re-measure alarm. Matching one literal compiler string here
        # made a harmless wording change look like a probe failure
        # (ADVICE r4).
        msg = str(exc)
        if "aligned to tiling" in msg:
            return "rejected: slice must be aligned to tiling (8)"
        return ("rejected (unrecognized wording — still a reject): "
                + (msg.splitlines() or ["<no message>"])[-1][-200:])
    raise AssertionError(
        "Mosaic now ACCEPTS sub-tile HBM DMA slices — the per-row kernel "
        "class exists after all; re-measure docs/W2V_KERNEL.md's verdict")


# ------------------------------------------------------------ measurement


def _measure_one(which: str) -> None:
    import jax
    import jax.numpy as jnp

    from tools.xprof_util import trace_device_ms

    if which == "subtile":
        print(f"SUBTILE {subtile_rejected()}")
        return

    table, idx, grads = _make_inputs()

    # The in-place ops DONATE the table (like the real training step):
    # without donation XLA prepends a ~73 MB defensive table copy inside
    # the traced jit_ span, inflating the in-place ops' ns/row. Donated
    # calls chain the result back in as the next call's operand.
    holder = [table]

    if which == "xla_scatter":
        fn = jax.jit(lambda t, i, g: t.at[i].add(g), donate_argnums=0)

        def run():
            holder[0] = fn(holder[0], idx, grads)
            return holder[0]
    elif which == "xla_gather":
        fn = jax.jit(lambda t, i: jnp.take(t, i, axis=0))

        def run():
            return fn(table, idx)
    elif which == "pallas_gather":
        fn = jax.jit(pallas_gather)
        ref = jnp.take(table, idx, axis=0)
        err = float(jnp.max(jnp.abs(fn(table, idx) - ref)))
        assert err == 0.0, f"pallas gather wrong: max err {err}"

        def run():
            return fn(table, idx)
    elif which == "pallas_rmw":
        check = jax.jit(pallas_rmw)
        ref = table.at[idx].add(grads)
        # duplicate rows accumulate in a different order → f32 rounding
        err = float(jnp.max(jnp.abs(check(table, idx, grads) - ref)))
        assert err < 1e-4, f"pallas rmw wrong: max err {err}"
        fn = jax.jit(pallas_rmw, donate_argnums=0)

        def run():
            holder[0] = fn(holder[0], idx, grads)
            return holder[0]
    else:
        raise SystemExit(f"unknown probe {which}")

    jax.block_until_ready(run())         # compile outside the trace
    ms = trace_device_ms(run, iters=5)
    print(f"DEVICE_MS {ms:.6f}")


def _measure(which: str) -> float:
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--_one", which],
        capture_output=True, text=True, timeout=500)
    for line in out.stdout.splitlines():
        if line.startswith("DEVICE_MS "):
            return float(line.split()[1])
    raise RuntimeError(f"probe {which} failed:\n{out.stdout[-2000:]}\n"
                       f"{out.stderr[-2000:]}")


def main(argv=None):
    if argv is None and len(sys.argv) >= 3 and sys.argv[1] == "--_one":
        _measure_one(sys.argv[2])
        return 0
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    sub = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--_one", "subtile"],
        capture_output=True, text=True, timeout=500)
    subtile = next((ln.partition(" ")[2] for ln in sub.stdout.splitlines()
                    if ln.startswith("SUBTILE ")), None)
    if subtile is None:
        raise RuntimeError(f"subtile probe failed:\n{sub.stdout[-2000:]}\n"
                           f"{sub.stderr[-2000:]}")
    print(f"sub-tile row DMA: {subtile}", flush=True)

    rows = {}
    for which in ("xla_scatter", "xla_gather", "pallas_gather",
                  "pallas_rmw"):
        ms = _measure(which)
        rows[which] = {"device_ms": round(ms, 3),
                       "ns_per_row": round(ms * 1e6 / N_ROWS, 1)}
        print(f"{which:14s} {ms:8.3f} ms   "
              f"{rows[which]['ns_per_row']:7.1f} ns/row", flush=True)

    if args.json:
        print(json.dumps({"vocab": VOCAB, "dim": DIM, "n_rows": N_ROWS,
                          "chunk": CHUNK, "depth": DEPTH, "tile": TILE,
                          "subtile_dma": subtile, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
