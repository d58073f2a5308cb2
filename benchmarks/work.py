"""The table of peaks, and the work an algorithm needs, from shapes.

Every roofline share and every ``*mfu*`` metric divides one of these
counts by a peak of this table and by a time from the device trace or
the window. They are the yardstick: a PR that claims a gain may not
edit this file.
"""

from __future__ import annotations

# One chip's published peaks, keyed by ``jax.Device.device_kind``.
# A kind that is not here is an error, not a default.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,        # FLOP/s
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 819 GB/s, 16 GB HBM per chip",
    },
}

# benchmarks/selfcheck.py and the tests run off the chip; whatever they
# compute with this row is plumbing, never a device number.
SELFCHECK_PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11,
                   "hbm_bytes": 1e9, "source": "selfcheck, not a device"}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmarks/work.py: no peaks on record for device kind "
            f"{device_kind!r}; add a row to PEAKS with its source")


# -- word2vec (skip-gram, negative sampling) -----------------------------------
def w2v_flops_per_pair(dim: int, negative: int) -> float:
    """Model FLOPs one trained pair needs: ``6 * dim * (negative + 1)``.

    A pair scores its centre row against 1 positive and ``negative``
    negative rows (a ``dim``-long dot each: 2*dim FLOPs), and each of
    those ``negative + 1`` scores sends a gradient to the centre row
    (2*dim) and to the output row (2*dim). Sharing a negative draw among
    G pairs changes which rows are touched, not this count. Source: the
    SGNS objective, Mikolov et al. 2013, eq. 4."""
    return 6.0 * dim * (negative + 1)


def w2v_bytes_per_step(batch: int, dim: int, negative: int, group: int,
                       itemsize: int) -> float:
    """HBM bytes one step of ``batch`` pairs has to move, whatever the
    update is implemented with: every touched row is read once and
    written once (a scatter-add reads, adds, writes).

    Rows: ``batch`` centre rows (input table), ``batch`` context rows
    and ``batch / group * negative`` shared negative rows (output
    table). ``group`` is ``shared_negatives`` (1 = a draw per pair).
    Indices and the candidate sampler's int traffic are left out (under
    1% of the rows at dim 300), so the share reads a little low."""
    rows = 2 * batch + (batch // max(group, 1)) * negative
    return 2.0 * rows * dim * itemsize


# -- transformer LM -------------------------------------------------------------
def lm_matmul_params(d_model: int, n_layers: int, d_ff: int, vocab: int) -> int:
    """Parameters that a token multiplies: per layer Q, K, V, O
    (4*d^2) and the two feed-forward matrices (2*d*d_ff), plus the
    ``d * vocab`` logits head. The embedding lookup is a gather."""
    return n_layers * (4 * d_model * d_model + 2 * d_model * d_ff) \
        + d_model * vocab


def lm_forward_flops_per_token(d_model: int, n_layers: int, d_ff: int,
                               vocab: int, context: float) -> float:
    """``2 * matmul params`` plus attention over ``context`` visible
    positions: QK^T and PV, 2 * 2 * context * d_model per layer."""
    return 2.0 * lm_matmul_params(d_model, n_layers, d_ff, vocab) \
        + 4.0 * context * d_model * n_layers


def train_flops_per_step(d_model: int, n_layers: int, d_ff: int, vocab: int,
                         batch: int, seq: int) -> float:
    """Copy of ``tools/lm_mfu.py``'s count with ``vocab`` an argument:
    training = 3 x forward (the backward pass does twice the forward's
    matmul work), attention causal-aware (an average visible span of
    seq/2). Recomputation is not counted."""
    return 3.0 * lm_forward_flops_per_token(
        d_model, n_layers, d_ff, vocab, seq / 2) * batch * seq


def causal_attention_work(batch: int, heads: int, seq: int, head_dim: int,
                          itemsize: int, backward: bool) -> tuple:
    """(FLOPs, bytes) of causal attention over ``batch * heads``
    programs. Forward: QK^T and PV over the lower triangle, 2 * 2 *
    (seq^2 / 2) * head_dim; reads Q, K, V and writes O once. Backward
    (flash form, scores recomputed once): dV, dP, dQ, dK and the score
    recompute, 5 matmuls against the forward's 2; reads Q, K, V, O, dO,
    writes dQ, dK, dV."""
    programs = batch * heads
    matmul = 2.0 * (seq * seq / 2) * head_dim          # one causal matmul
    tensor = seq * head_dim * itemsize
    if backward:
        return programs * 5 * matmul, programs * 8 * tensor
    return programs * 2 * matmul, programs * 4 * tensor


def decode_step_bytes(d_model: int, n_layers: int, d_ff: int, vocab: int,
                      live_tokens: float, itemsize: int) -> float:
    """HBM bytes one decode step needs: every matmul parameter and the
    embedding/head once, plus K and V of the LIVE tokens of the slots
    that decode (``2 * n_layers * d_model`` values a token), not of the
    gathered ``slots x T`` view."""
    params = lm_matmul_params(d_model, n_layers, d_ff, vocab)
    return itemsize * (params + 2.0 * n_layers * d_model * live_tokens)
