"""CIFAR-10-class ResNet trained data-parallel through the binding.

Reproduces the reference's headline benchmark SHAPE (ResNet-32 on CIFAR-10
through the Python binding's param manager —
``binding/python/docs/BENCHMARK.md:33-57`` and
``examples/theano/lasagne/Deep_Residual_Learning_CIFAR-10.py`` in the
Multiverso reference) on this stack: the model is the same depth-6n+2
CIFAR ResNet family (n=5 -> ResNet-32, 464,154 params) written in plain
JAX, and parameter sync rides ``multiverso.jax_ext.MVNetParamManager``
exactly like the reference rode ``lasagne_ext.MVNetParamManager``.

No network egress in this environment, so the dataset is synthetic
CIFAR-shaped data (32x32x3, 10 classes; class templates + noise) — sec/epoch
and DP scaling are hardware-true, accuracy is meaningful only relative to
the same dataset's single-worker baseline.

Single worker:
    python cifar_resnet.py -epochs 2
Data-parallel (per process, under the MV_* coordinator env):
    python cifar_resnet.py -mv 1 -sync_every 1 -epochs 2
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir))
_REPO = os.path.abspath(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                     *[os.pardir] * 3))
sys.path.insert(0, _REPO)


# -- model: CIFAR ResNet (He et al. sec 4.2: 6n+2 layers, widths 16/32/64) --

def _conv(x, w, stride=1):
    import jax

    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, scale, bias):
    import jax.numpy as jnp

    # batch-norm without running stats (training-mode normalisation only;
    # the reference benchmark also trains/evals in-distribution)
    mean = x.mean(axis=(0, 1, 2), keepdims=True)
    var = x.var(axis=(0, 1, 2), keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-5) * scale + bias


def init_resnet(rng: np.random.Generator, n: int = 5, num_classes: int = 10):
    """Params for ResNet-(6n+2); n=5 -> ResNet-32 with 464,154 params."""
    # strides are STATIC structure (ints must not ride the grad pytree)
    params = {"stem": _he(rng, (3, 3, 3, 16)), "stem_s": np.ones(16, np.float32),
              "stem_b": np.zeros(16, np.float32), "blocks": []}
    strides = []
    widths = [16, 32, 64]
    w_in = 16
    for stage, w in enumerate(widths):
        for block in range(n):
            stride = 2 if (stage > 0 and block == 0) else 1
            blk = {
                "c1": _he(rng, (3, 3, w_in, w)),
                "s1": np.ones(w, np.float32), "b1": np.zeros(w, np.float32),
                "c2": _he(rng, (3, 3, w, w)),
                "s2": np.ones(w, np.float32), "b2": np.zeros(w, np.float32),
                "proj": (_he(rng, (1, 1, w_in, w)) if (stride != 1 or w_in != w)
                         else None),
            }
            params["blocks"].append(blk)
            strides.append(stride)
            w_in = w
    params["fc_w"] = (rng.standard_normal((64, num_classes)) * 0.01).astype(
        np.float32)
    params["fc_b"] = np.zeros(num_classes, np.float32)
    return params, tuple(strides)


def _he(rng, shape):
    fan_in = int(np.prod(shape[:-1]))
    return (rng.standard_normal(shape) * np.sqrt(2.0 / fan_in)).astype(
        np.float32)


def count_params(params) -> int:
    import jax

    return int(sum(np.prod(np.shape(p))
                   for p in jax.tree_util.tree_leaves(params)))


def forward(params, x, strides):
    import jax
    import jax.numpy as jnp

    h = jax.nn.relu(_bn(_conv(x, params["stem"]),
                        params["stem_s"], params["stem_b"]))
    for blk, stride in zip(params["blocks"], strides):
        shortcut = h
        h2 = jax.nn.relu(_bn(_conv(h, blk["c1"], stride),
                             blk["s1"], blk["b1"]))
        h2 = _bn(_conv(h2, blk["c2"]), blk["s2"], blk["b2"])
        if blk["proj"] is not None:
            shortcut = _conv(shortcut, blk["proj"], stride)
        h = jax.nn.relu(h2 + shortcut)
    h = h.mean(axis=(1, 2))                      # global average pool
    return h @ params["fc_w"] + params["fc_b"]


# -- synthetic CIFAR-shaped data --------------------------------------------

def make_dataset(n_train: int, n_test: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    templates = rng.standard_normal((10, 32, 32, 3)).astype(np.float32)
    def draw(n, salt):
        r = np.random.default_rng(seed + salt)
        y = r.integers(0, 10, n)
        x = templates[y] * 0.6 + r.standard_normal(
            (n, 32, 32, 3)).astype(np.float32)
        return x.astype(np.float32), y.astype(np.int32)
    return draw(n_train, 1), draw(n_test, 2)


# -- training ----------------------------------------------------------------

def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)

    def opt(name, default, cast):
        flag = f"-{name}"
        if flag in argv:
            i = argv.index(flag)
            val = cast(argv[i + 1])
            del argv[i:i + 2]
            return val
        return default

    use_mv = bool(opt("mv", 0, int))
    sync_every = opt("sync_every", 1, int)
    epochs = opt("epochs", 2, int)
    n_train = opt("train", 10000, int)
    n_test = opt("test", 2000, int)
    batch = opt("batch", 128, int)
    depth_n = opt("n", 5, int)          # 6n+2 depth; 5 -> ResNet-32
    lr = opt("lr", 0.1, float)
    json_out = opt("json", "", str)

    import jax
    import jax.numpy as jnp
    import optax

    worker_id, workers = 0, 1
    if use_mv:
        import multiverso as mv
        from multiverso.jax_ext import MVNetParamManager

        mv.init(sync=True)
        worker_id, workers = mv.worker_id(), mv.workers_num()

    (x_train, y_train), (x_test, y_test) = make_dataset(n_train, n_test)
    # each worker trains its contiguous shard (reference: per-process
    # minibatch streams)
    shard = n_train // workers
    x_local = x_train[worker_id * shard:(worker_id + 1) * shard]
    y_local = y_train[worker_id * shard:(worker_id + 1) * shard]

    params, strides = init_resnet(np.random.default_rng(42), n=depth_n)
    n_params = count_params(params)

    tx = optax.sgd(lr, momentum=0.9)
    opt_state = tx.init(params)

    @jax.jit
    def train_step(params, opt_state, x, y):
        def loss_fn(p):
            logits = forward(p, x, strides)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    @jax.jit
    def eval_logits(params, x):
        return forward(params, x, strides)

    manager = None
    if use_mv:
        manager = MVNetParamManager(params)
        params = manager.params

    steps_per_epoch = max(1, x_local.shape[0] // batch)
    epoch_times = []
    loss = jnp.float32(0)
    for epoch in range(epochs):
        t0 = time.perf_counter()
        perm = np.random.default_rng(epoch * 131 + worker_id).permutation(
            x_local.shape[0])
        for step in range(steps_per_epoch):
            idx = perm[step * batch:(step + 1) * batch]
            params, opt_state, loss = train_step(
                params, opt_state, jnp.asarray(x_local[idx]),
                jnp.asarray(y_local[idx]))
            if manager is not None and (step + 1) % sync_every == 0:
                manager.set_params(params)
                params = manager.sync_all_param()
        # value fetch forces the full dispatch chain to complete
        float(loss)
        if manager is not None:   # epoch barrier like the reference run
            import multiverso as mv

            mv.barrier()
        epoch_times.append(time.perf_counter() - t0)

    # test accuracy (every worker evaluates the shared params)
    correct = 0
    for i in range(0, x_test.shape[0], 500):
        logits = np.asarray(eval_logits(params, jnp.asarray(x_test[i:i + 500])))
        correct += int((logits.argmax(-1) == y_test[i:i + 500]).sum())
    acc = correct / x_test.shape[0]

    result = {
        "workers": workers, "worker_id": worker_id, "mv": use_mv,
        "sync_every": sync_every, "depth": 6 * depth_n + 2,
        "params": n_params, "batch": batch,
        "sec_per_epoch": round(float(np.mean(epoch_times[1:] or epoch_times)),
                               3),
        "final_loss": round(float(loss), 4),
        "test_acc": round(acc, 4),
        "platform": jax.devices()[0].platform,
    }
    print("RESULT " + json.dumps(result), flush=True)
    if json_out:
        with open(json_out, "w") as f:
            json.dump(result, f)
    if use_mv:
        import multiverso as mv

        mv.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
