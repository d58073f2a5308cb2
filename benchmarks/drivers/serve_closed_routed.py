"""Driver ``serve_closed_routed``: ``serve_closed_cfg``'s closed loop,
window and release, with a comparison made for a model whose router
NORMALISES its gates.

There an 8th-against-9th pick that flips on bfloat16 rounding swaps a
whole expert at a whole gate, so the WIDEST gap of a served token
(``serve_closed.compare``'s ``token_logit_gap``) reads the rounding of a
sound program, a lower precision and a wrong pick alike, and a weight
law that keeps it small hides the routed experts from it (PERF.md
section 6, PR 32). What separates them is how OFTEN a served token lies
below the reference's best and by how much: this driver compares

* ``token_logit_gap_mean``: over every served token of the sample, the
  mean of the gap by which its logit lies below the best logit of the
  reference's one full forward pass at its position (0 for most). A
  disturbance of the logits moves tokens in proportion to its size and
  each by its size, so the mean grows with its square and a rare flip
  weighs little;
* ``short_answers``, as ``serve_closed`` has it.

The widest gap and the share of moved tokens go to ``info.checked``,
uncompared. The reference hands out the per-token gaps
(``token_gap_tables``) and plants the faults (``FAULTS``) that
``controls`` reads beside the 8-bit-float control, each on top of
bfloat16 operands, which is what a faulty program would run in.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import load_module

_cfg = load_module("drivers", "serve_closed_cfg")
build, window, release = _cfg.build, _cfg.window, _cfg.release


def compare(tables: list, got: dict, limits: dict) -> list:
    gaps = np.concatenate(tables) if tables else np.zeros(0, np.float32)
    rows = [("token_logit_gap_mean",
             float(gaps.mean()) if gaps.size else None),
            ("short_answers", float(got["short_answers"])
             if got["finished"] else None)]
    return [{"name": n, "value": v if v is None or np.isfinite(v) else None,
             "limit": limits[n]} for n, v in rows]


def check(got: dict, ctx) -> list:
    ref = load_module("reference", ctx.config_name)
    tables = ref.token_gap_tables(ctx.config, ctx.seed31, got["sequences"],
                                  got["prompt_lens"])
    gaps = np.concatenate(tables) if tables else np.zeros(0, np.float32)
    ctx.counters["checked"] = {
        "requests": len(tables), "served_tokens": int(gaps.size),
        "gap_max": [float(t.max()) if t.size else 0.0 for t in tables],
        "moved_share": float((gaps > 0).mean()) if gaps.size else None}
    return compare(tables, got, ctx.traffic["limits"])


def controls(got: dict, ctx) -> dict:
    """No control decodes: at each position of the same prompts and
    served tokens, the gap (in the exact reference's logits) of the
    token that the reference puts first with 8-bit-float matmuls, and
    with each planted fault under bfloat16 matmuls."""
    ref = load_module("reference", ctx.config_name)
    args = (ctx.config, ctx.seed31, got["sequences"], got["prompt_lens"])
    exact = ref.padded_logits(*args[:3])
    out = {"control_float8_e4m3fn": ("float8_e4m3fn", "")}
    out.update({f"fault_{f}": ("bfloat16", f) for f in ref.FAULTS})
    return {name: compare(ref.token_gap_tables(*args, compute, fault,
                                               exact=exact),
                          got, ctx.traffic["limits"])
            for name, (compute, fault) in out.items()}
