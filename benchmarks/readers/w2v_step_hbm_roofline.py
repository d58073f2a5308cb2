"""``w2v_step_hbm_roofline``: the least time HBM could take for one
step (``work.w2v_bytes_per_step`` of one worker's batch / peak bytes/s;
the memory bound binds: the FLOP bound is ~50x lower) over the step's
device time, in percent. Independent of ``update_impl``."""

import jax.numpy as jnp

from benchmarks import work
from benchmarks.harness import load_module


def read(ctx):
    step_ms = load_module("readers", "w2v_step_device_ms").read(ctx)
    if not step_ms:
        return None
    cfg = ctx.config
    nbytes = work.w2v_bytes_per_step(
        cfg["batch_size_per_worker"], cfg["embedding_size"], cfg["negative"],
        cfg["shared_negatives"], jnp.dtype(cfg["table_dtype"]).itemsize)
    return 100.0 * (nbytes / ctx.peaks["hbm_bytes_per_s"]) / (step_ms / 1e3)
