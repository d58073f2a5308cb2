"""On-chip probe of one MLA sublayer of a 512-row prefill chunk, its
expanded attention dense over the whole logical cache against the key
tiles the chunk can reach (run by no cell).

At GLM-5's shapes (64 heads, keys 192 + 64, values 256, ``t_logical``
8,704) and LongCat's (64 heads, 128 + 64, 128, 2,304), with random
bfloat16 queries, cache rows and ``W_kvb`` / ``W_o``, it prices, in
device ms (profiler, ``tools/xprof_util``):

- ``dense``: ``longcat.mla_expanded`` under the chunk's causal mask over
  every position, what the chunk ran before the tiles;
- ``tiled_<tk>``: ``longcat.mla_expanded_tiled`` at key tile ``tk``, the
  loop's trip count traced from the chunk's offset and length,

for chunks at offsets 0, the middle and the last chunk, each against
the dense output (``gap``: the widest difference over the widest dense
output). One process; prints one JSON document.

Usage: python tools/mla_chunk_probe.py [--models glm5,longcat]
       [--tiles 256,512] [--iters 10]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

CHUNK = 512
# heads, nope, rope, value width, latent rank, hidden, t_logical
MODELS = {"glm5": (64, 192, 64, 256, 512, 6144, 8704),
          "longcat": (64, 128, 64, 128, 512, 6144, 2304)}


def layer_inputs(H, dn, dr, dv, rkv, D, T, seed=0):
    """``(cfg, w, q_nope, q_rope, rows)`` of one sublayer at these sizes,
    in bfloat16, drawn on the device."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.models import longcat

    cfg = longcat.LongCatConfig(
        hidden_size=D, num_attention_heads=H, kv_lora_rank=rkv,
        qk_nope_head_dim=dn, qk_rope_head_dim=dr, v_head_dim=dv)
    k = jax.random.split(jax.random.PRNGKey(seed), 5)

    def draw(key, shape, std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(
            jnp.bfloat16)

    w = {"w_kvb": draw(k[0], (rkv, H * (dn + dv)), rkv ** -0.5),
         "w_o": draw(k[1], (H * dv, D), (H * dv) ** -0.5)}
    rows = draw(k[2], (T, cfg.pool_width), 1.0)
    q_nope = draw(k[3], (CHUNK, H, dn), 1.5)
    q_rope = draw(k[4], (CHUNK, H, dr), 1.5)
    return cfg, w, q_nope, q_rope, rows


def chunk_mask(T, offset):
    import jax.numpy as jnp

    return jnp.arange(T)[None, :] <= (offset + jnp.arange(CHUNK))[:, None]


def _dense(cfg, w, q_nope, q_rope, rows, mask, reach):
    del reach
    from multiverso_tpu.models import longcat

    return longcat.mla_expanded(cfg, w, q_nope, q_rope, rows, mask)


def _tiled(cfg, tile, w, q_nope, q_rope, rows, mask, reach):
    from multiverso_tpu.models import longcat

    longcat.KEY_TILE = tile     # read while this traces: once a (cfg, tile)
    return longcat.mla_expanded_tiled(cfg, w, q_nope, q_rope, rows, mask,
                                      reach)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--models", default="glm5,longcat")
    ap.add_argument("--tiles", default="256,512")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from tools.xprof_util import trace_device_ms

    if jax.default_backend() != "tpu":
        print("mla_chunk_probe: needs a TPU", file=sys.stderr)
        return 3

    def timed(fn, *a):
        out = fn(*a)
        return out, trace_device_ms(lambda: fn(*a), args.iters)

    # weights are arguments, never constants
    dense = jax.jit(_dense, static_argnums=0)
    tiled = jax.jit(_tiled, static_argnums=(0, 1))
    tiles = [int(t) for t in args.tiles.split(",")]
    out = {}
    for name in args.models.split(","):
        H, dn, dr, dv, rkv, D, T = MODELS[name]
        cfg, w, q_nope, q_rope, rows = layer_inputs(H, dn, dr, dv, rkv, D, T)
        res = {}
        for offset in (0, (T // 2) // CHUNK * CHUNK, T - CHUNK):
            a = (w, q_nope, q_rope, rows, chunk_mask(T, offset),
                 jnp.int32(offset + CHUNK))
            want, ms = timed(dense, cfg, *a)
            scale = float(jnp.abs(want).max())
            row = {"dense_ms": ms}
            for tk in tiles:
                got, ms = timed(tiled, cfg, tk, *a)
                row[f"tiled_{tk}_ms"] = ms
                row[f"tiled_{tk}_gap"] = float(
                    jnp.abs(got - want).max()) / scale
            res[str(offset)] = row
            print(json.dumps({name: {offset: row}}), flush=True)
        out[name] = res
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
