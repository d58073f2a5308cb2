"""Driver ``lm_train``: ``TransformerLM.train_batch`` back to back on
seeded ``[batch, seq]`` token batches.

Set-up builds ONE ``TransformerLM`` (compiled step, parameters,
momentum), drives it through its first ``check_steps`` steps with the
window's own call and feed on batches whose rows all differ, takes the
program's readings for ``correct`` there (each loss; per leaf the norm
of the first gradient, which is the momentum after one step from zero;
per leaf the norm of the parameters' change after the last), and hands
the same object to the window.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from benchmarks import gen, tracered
from benchmarks.harness import load_module

# the program's parameter tree, in the reference's leaf order
LEAVES = ("embed", "pos", "ln1_g", "ln2_g", "w_q", "w_k", "w_v", "w_o",
          "w_ff1", "w_ff2", "ln_f_g")


def _leaf(tree, name):
    return tree[name] if name in tree else tree["layers"][name]


@functools.cache
def _norms_fn():
    import jax
    import jax.numpy as jnp

    def norms(tree, base):
        out = []
        for n in LEAVES:
            x = _leaf(tree, n).astype(jnp.float32)
            if base is not None:
                x = x - _leaf(base, n).astype(jnp.float32)
            out.append(jnp.sqrt(jnp.sum(jnp.square(x))))
        return out

    return jax.jit(norms)


def _batches(ctx):
    t = ctx.traffic
    return gen.lm_batches(ctx.seed, t["device_batches"], t["batch"], t["seq"],
                          ctx.config["vocab_size"])


def make_model(ctx):
    import jax.numpy as jnp

    from multiverso_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)

    cfg = ctx.config
    return TransformerLM(TransformerConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
        n_heads=cfg["n_head"], n_layers=cfg["n_layer"], d_ff=cfg["n_inner"],
        max_seq=cfg["n_positions"], dtype=jnp.dtype(cfg["dtype"]),
        learning_rate=cfg["learning_rate"], momentum=cfg["momentum"],
        seed=ctx.seed31, attention=ctx.traffic.get("attention", "flash")))


def build(ctx, mv):
    import jax
    import jax.numpy as jnp

    t = ctx.traffic
    lm = make_model(ctx)
    ctx.mark("model")
    batches = _batches(ctx)
    p0 = jax.tree.map(jnp.copy, lm.params)
    got = {"loss": [], "first": None, "last": None}
    for i in range(int(t["check_steps"])):
        got["loss"].append(float(lm.train_batch(batches[i])))
        if i == 0:
            got["first"] = [float(x) for x in _norms_fn()(lm._momentum, None)]
    got["last"] = [float(x) for x in _norms_fn()(lm.params, p0)]
    del p0
    ctx.mark("checked_steps")
    # the allocator's peak leaves out what a running program holds for
    # itself (logits, activations): the compiled step says how much
    temp = lm._step.lower(lm.params, lm._momentum, batches[0]).compile() \
        .memory_analysis().temp_size_in_bytes
    return {"lm": lm, "batches": batches, "got": got,
            "next": int(t["check_steps"]), "program_temp_bytes": int(temp)}


def window(state, ctx, seconds: float) -> dict:
    """Steps back to back: step i is sent, then the loss of step i-1 is
    fetched (a fetch a step, as the LM app makes), so one step is always
    queued. The clock closes on the fetch of the last loss."""
    lm, batches, i = state["lm"], state["batches"], state["next"]
    n = batches.shape[0]
    losses, pending = [], None
    t0 = time.perf_counter()
    while True:
        with tracered.span("step"):
            sent = lm.train_batch(batches[i % n])
        i += 1
        if pending is not None:
            with tracered.span("fetch"):
                losses.append(float(pending))
        pending = sent
        if time.perf_counter() - t0 >= seconds:
            break
    with tracered.span("fetch"):
        losses.append(float(pending))
    elapsed = time.perf_counter() - t0
    tokens = len(losses) * batches.shape[1] * batches.shape[2]
    ctx.counters.update(
        attempted=len(losses), failed=int(np.sum(~np.isfinite(losses))),
        steps=len(losses), tokens=tokens, elapsed_s=elapsed,
        first_loss=losses[0], last_loss=losses[-1])
    return {"train_tokens_per_s": tokens / elapsed}


def release(state, ctx) -> dict:
    state.pop("lm")
    state.pop("batches")
    return state["got"]


def compare(got: dict, ref: dict, limits: dict) -> list:
    """Each number compared, beside its limit (``traffic.limits``). The
    norms go by the worst leaf: the gap between the program's norm and
    the reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Leaves whose first gradient in the
    reference is under a thousandth of the median leaf's move by
    round-off alone and are left out of the change."""
    g_ref = np.asarray(ref["first"])
    moved = g_ref >= 1e-3 * np.median(g_ref)

    def worst(a, b, keep):
        a, b = np.asarray(a)[keep], np.asarray(b)[keep]
        return float(np.max(np.abs(a - b) / np.maximum(b, np.median(b))))

    rows = [
        ("loss_gap", max(abs(a - b) / abs(b)
                         for a, b in zip(got["loss"], ref["loss"]))),
        ("first_grad_norm_gap", worst(got["first"], ref["first"],
                                      np.ones_like(moved))),
        ("change_norm_gap", worst(got["last"], ref["last"], moved)),
    ]
    return [{"name": n, "value": v if np.isfinite(v) else None,
             "limit": limits[n]} for n, v in rows]


def check(got: dict, ctx) -> list:
    t = ctx.traffic
    batches = _batches(ctx)
    ref = load_module("reference", ctx.config_name).follow(
        ctx.config, ctx.seed31, batches, len(got["loss"]))
    ctx.counters["reference"] = ref
    ctx.counters["program"] = got
    return compare(got, ref, t["limits"])


def controls(got: dict, ctx) -> dict:
    """The reference put in the program's place, held against the sound
    reference: every matmul's operands in an 8-bit float (the control:
    the nearest precision below bfloat16), and the planted faults."""
    t = ctx.traffic
    ref = load_module("reference", ctx.config_name)
    sound = ctx.counters["reference"]
    batches = _batches(ctx)

    def against(**kw):
        return compare(ref.follow(ctx.config, ctx.seed31, batches,
                                  len(got["loss"]), **kw), sound, t["limits"])

    return {"control_float8_e4m3fn": against(compute="float8_e4m3fn"),
            "fault_half_batch": against(fault="half_batch")}
