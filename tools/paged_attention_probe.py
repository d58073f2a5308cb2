"""On-chip probe of ``ops.paged_attention.paged_mq_attention`` at the two
serving cells' shapes.

For each shape (GPT-2 small: two ``[12 x 8193, 16, 768]`` pools, 12
heads spread over 768 lanes; LongCat: one ``[8 x 18433, 16, 640]``
latent pool, 64 heads, values the first 512 lanes) it draws pools,
block tables and ragged lengths (``--live`` of the slots live,
lengths uniform in the shape's range), runs the kernel for every
``--tile-blocks`` and the gathered-view attention it replaces, and
prints, a layer: device ms (profiler, ``tools/xprof_util``), the live
blocks copied, and the widest gap to the view's result. One process.

Usage: python tools/paged_attention_probe.py [--shapes gpt2,longcat]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# slots, heads, row width, value width, blocks a slot, layers, pools,
# (shortest, longest) live length
SHAPES = {
    "gpt2": dict(S=128, H=12, W=768, wv=768, M=64, G=12, pools=2,
                 lengths=(40, 560)),
    "longcat": dict(S=128, H=64, W=640, wv=512, M=144, G=8, pools=1,
                    lengths=(128, 1400)),
}
_BS = 16


def _view_attention(q, k_pool, v_pool, tables, lengths, scale, wv):
    """What the kernel replaces: gather every slot's blocks, two
    products over the view (``_cached_attention`` / ``mla_latent``)."""
    import jax
    import jax.numpy as jnp

    S, M = tables.shape
    view = lambda pool: jnp.take(pool, tables, axis=0, mode="clip").reshape(
        S, M * _BS, -1)
    if v_pool is None:
        # one view, held between its two readers (longcat's barrier)
        kv = vv = jax.lax.optimization_barrier(view(k_pool))
    else:
        kv, vv = view(k_pool), view(v_pool)
    s = jnp.einsum("shw,stw->sht", q, kv,
                   preferred_element_type=jnp.float32) * scale
    live = jnp.arange(M * _BS)[None, :] < lengths[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None], s, -1e30), axis=-1)
    return jnp.einsum("sht,stw->shw", p.astype(vv.dtype), vv[..., :wv],
                      preferred_element_type=jnp.float32)


def probe(name: str, live: float, tile_blocks, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.ops.paged_attention import paged_mq_attention
    from tools.xprof_util import trace_device_ms

    c = SHAPES[name]
    S, H, W, wv, M, G = (c[k] for k in ("S", "H", "W", "wv", "M", "G"))
    N = S * M + 1
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    pools = [jax.random.normal(k, (G * N, _BS, W), jnp.bfloat16)
             for k in jax.random.split(key, c["pools"])]
    k_pool, v_pool = pools[0], (pools[1] if c["pools"] == 2 else None)
    q = jnp.asarray(rng.standard_normal((S, H, W)) * 0.05, jnp.bfloat16)
    lengths = np.where(rng.random(S) < live,
                       rng.integers(*c["lengths"], S), 0).astype(np.int32)
    tables = (1 + rng.permutation(S * M)).reshape(S, M).astype(np.int32)
    blocks = int(np.sum(-(-lengths // _BS)))
    args = (q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths))
    scale = 0.125

    def layers(attend):
        # every layer's call, as a step makes them: the layer's base in
        # the tables
        def run(q, k_pool, v_pool, tables, lengths):
            out = 0.0
            for g in range(G):
                out = out + attend(q, k_pool, v_pool, g * N + tables,
                                   lengths)
            return out
        return jax.jit(run)

    view = layers(lambda *a: _view_attention(*a, scale, wv))
    want = np.asarray(view(*args))
    out = {"shape": name, "live_slots": int(np.sum(lengths > 0)),
           "live_blocks_a_layer": blocks,
           "kv_live_block_share": blocks / (S * M),
           "view_ms_a_layer": trace_device_ms(lambda: view(*args)) / G,
           "kernel": {}}
    alive = lengths > 0
    for tb in tile_blocks:
        fn = layers(lambda *a, tb=tb: paged_mq_attention(
            *a, scale=scale, wv=wv, tile_blocks=tb))
        got = np.asarray(fn(*args))
        out["kernel"][tb] = {
            "ms_a_layer": trace_device_ms(lambda: fn(*args)) / G,
            "max_gap_to_view": float(np.abs(got[alive] - want[alive]).max()),
            "finite": bool(np.isfinite(got).all())}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--shapes", default="gpt2,longcat")
    ap.add_argument("--tile-blocks", default="8,16,32")
    ap.add_argument("--live", type=float, default=None,
                    help="share of slots live (default: 0.52 gpt2, "
                         "0.98 longcat, the cells' occupancy)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    import jax

    if jax.default_backend() != "tpu":
        print("paged_attention_probe: needs the chip", file=sys.stderr)
        return 3
    for name in args.shapes.split(","):
        live = args.live if args.live is not None else (
            0.52 if name == "gpt2" else 0.98)
        print(json.dumps(probe(
            name, live, [int(t) for t in args.tile_blocks.split(",")],
            args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
