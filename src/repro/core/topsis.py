"""Vectorized, jittable TOPSIS engine — the paper's core contribution.

TOPSIS (Technique for Order Preference by Similarity to Ideal Solution)
ranks N alternatives (cluster nodes / TPU slices) over C criteria:

  1. vector-normalize the decision matrix column-wise,
  2. apply criterion weights,
  3. form the ideal (A+) and anti-ideal (A-) alternatives,
  4. compute Euclidean distances d+ and d-,
  5. closeness coefficient  CC_i = d-_i / (d+_i + d-_i)  in [0, 1],
  6. rank descending by CC.

Everything here is pure jnp so it jits, vmaps (batched pods), and lowers to
TPU. A Pallas kernel for the tiled hot-path lives in
``repro.kernels.topsis_pallas``; this module is its semantic reference for the
*whole* pipeline (the kernel consumes precomputed column norms).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

_EPS = 1e-12


class TopsisResult(NamedTuple):
    closeness: jax.Array      # (N,) closeness coefficient per alternative
    ranking: jax.Array        # (N,) indices, best alternative first
    d_pos: jax.Array          # (N,) distance to ideal
    d_neg: jax.Array          # (N,) distance to anti-ideal
    weighted: jax.Array       # (N, C) weighted normalized matrix


def normalize_matrix(matrix: jax.Array) -> jax.Array:
    """Column-wise vector normalization: r_ij = x_ij / ||x_:j||_2.

    Zero columns normalize to zero (all alternatives equal on that
    criterion → it contributes nothing to the ranking).
    """
    norms = jnp.sqrt(jnp.sum(matrix * matrix, axis=-2, keepdims=True))
    return matrix / jnp.maximum(norms, _EPS)


def ideal_points(weighted: jax.Array, benefit: jax.Array):
    """Ideal / anti-ideal rows. ``benefit`` is a (C,) bool mask:
    True → higher is better (max enters A+), False → cost criterion."""
    col_max = jnp.max(weighted, axis=-2)
    col_min = jnp.min(weighted, axis=-2)
    a_pos = jnp.where(benefit, col_max, col_min)
    a_neg = jnp.where(benefit, col_min, col_max)
    return a_pos, a_neg


def masked_ideal_points(weighted: jax.Array, benefit: jax.Array,
                        valid: jax.Array | None):
    """Ideal / anti-ideal rows with infeasible alternatives excluded from
    BOTH reference points: invalid rows are replaced with the worst possible
    value for A+ and the best possible value for A- so they can never define
    either extreme. The single source of this rule — the Pallas wrappers in
    ``repro.kernels.ops`` share it (``closeness_np`` mirrors it in numpy)."""
    if valid is None:
        return ideal_points(weighted, benefit)
    worst = jnp.where(benefit, -jnp.inf, jnp.inf)
    best = jnp.where(benefit, jnp.inf, -jnp.inf)
    a_pos, _ = ideal_points(jnp.where(valid[..., None], weighted, worst),
                            benefit)
    _, a_neg = ideal_points(jnp.where(valid[..., None], weighted, best),
                            benefit)
    return a_pos, a_neg


def closeness(matrix: jax.Array, weights: jax.Array, benefit: jax.Array,
              valid: jax.Array | None = None) -> TopsisResult:
    """Full TOPSIS pipeline on a (N, C) decision matrix.

    ``valid`` is an optional (N,) bool mask for alternatives that survived
    filtering (infeasible nodes). Invalid rows are excluded from the ideal
    points and receive closeness -inf so they never rank first.
    """
    weights = weights / jnp.maximum(jnp.sum(weights), _EPS)
    r = normalize_matrix(matrix)
    v = r * weights

    a_pos, a_neg = masked_ideal_points(v, benefit, valid)

    d_pos = jnp.sqrt(jnp.sum((v - a_pos) ** 2, axis=-1))
    d_neg = jnp.sqrt(jnp.sum((v - a_neg) ** 2, axis=-1))
    cc = d_neg / jnp.maximum(d_pos + d_neg, _EPS)
    # Degenerate case: single feasible alternative or all-equal matrix.
    cc = jnp.where(d_pos + d_neg <= _EPS, 0.5, cc)
    if valid is not None:
        cc = jnp.where(valid, cc, -jnp.inf)
    ranking = jnp.argsort(-cc, axis=-1)
    return TopsisResult(cc, ranking, d_pos, d_neg, v)


@functools.partial(jax.jit, static_argnames=())
def closeness_jit(matrix, weights, benefit, valid):
    return closeness(matrix, weights, benefit, valid)


def select(matrix: jax.Array, weights: jax.Array, benefit: jax.Array,
           valid: jax.Array | None = None) -> jax.Array:
    """Index of the best alternative (argmax closeness)."""
    return closeness(matrix, weights, benefit, valid).ranking[..., 0]


# Batched form: P concurrent pods, each with its own (N, C) matrix + weights.
batched_closeness = jax.vmap(closeness, in_axes=(0, 0, None, 0))


def batched_closeness_np(mats, ws, benefit, valids=None) -> "np.ndarray":
    """(P, N) closeness via a per-pod :func:`closeness_np` loop — the
    reference semantics the batched jax/pallas backends must match."""
    import numpy as np
    out = [closeness_np(m, w, benefit,
                        None if valids is None else valids[i]).closeness
           for i, (m, w) in enumerate(zip(mats, ws))]
    return np.stack(out, axis=0)


# --- Weight-scheme grid: (S schemes x P pods x N nodes) ---
def closeness_grid_np(mats, ws, benefit, valids=None) -> "np.ndarray":
    """(S, P, N) closeness for a (P, N, C) queue tensor under an (S, C)
    weight-scheme grid: a per-scheme loop of :func:`batched_closeness_np`,
    so row ``s`` is bitwise equal to scoring the queue under ``ws[s]``
    alone — the oracle the jax and pallas grid rounds of
    ``BatchScheduler.score_queue_grid`` are verified against (1e-5,
    float32 device math)."""
    import numpy as np
    ws = np.asarray(ws, dtype=np.float64)
    p = len(mats)
    return np.stack([
        batched_closeness_np(mats, np.broadcast_to(w, (p, w.shape[-1])),
                             benefit, valids)
        for w in ws], axis=0)


def _weighted_and_ideals_np(matrix, weights, benefit, valid):
    """The numpy pipeline up to the distance step: weighted normalized
    matrix plus the (masked) ideal / anti-ideal rows — shared verbatim by
    :func:`closeness_np` and :func:`explain_np` so the explanation is an
    exact decomposition of the scores the scheduler acted on."""
    import numpy as np
    matrix = np.asarray(matrix, dtype=np.float64)
    weights = np.asarray(weights, dtype=np.float64)
    weights = weights / max(weights.sum(), _EPS)
    benefit = np.asarray(benefit, dtype=bool)
    norms = np.sqrt((matrix * matrix).sum(axis=0, keepdims=True))
    v = matrix / np.maximum(norms, _EPS) * weights
    if valid is not None:
        valid = np.asarray(valid, dtype=bool)
        worst = np.where(benefit, -np.inf, np.inf)
        best = np.where(benefit, np.inf, -np.inf)
        vw = np.where(valid[:, None], v, worst)
        vb = np.where(valid[:, None], v, best)
        a_pos = np.where(benefit, vw.max(axis=0), vw.min(axis=0))
        a_neg = np.where(benefit, vb.min(axis=0), vb.max(axis=0))
    else:
        a_pos = np.where(benefit, v.max(axis=0), v.min(axis=0))
        a_neg = np.where(benefit, v.min(axis=0), v.max(axis=0))
    return v, a_pos, a_neg, valid


def closeness_np(matrix, weights, benefit, valid=None):
    """NumPy mirror of :func:`closeness` for latency-critical single
    decisions on CPU (the per-pod scheduler hot path, where jnp dispatch
    overhead dominates the 4-node matrices of the paper's cluster).
    Semantics are identical; tests assert equivalence."""
    import numpy as np
    v, a_pos, a_neg, valid = _weighted_and_ideals_np(matrix, weights,
                                                     benefit, valid)
    # inf/inf -> nan is expected when NO row is valid (both ideals are
    # +-inf); the nan closeness is masked to -inf below
    with np.errstate(invalid="ignore"):
        d_pos = np.sqrt(((v - a_pos) ** 2).sum(axis=1))
        d_neg = np.sqrt(((v - a_neg) ** 2).sum(axis=1))
        cc = d_neg / np.maximum(d_pos + d_neg, _EPS)
        cc = np.where(d_pos + d_neg <= _EPS, 0.5, cc)
    if valid is not None:
        cc = np.where(valid, cc, -np.inf)
    return TopsisResult(cc, np.argsort(-cc), d_pos, d_neg, v)


def _cc_row_np(row, a_pos, a_neg):
    """Closeness of one weighted-normalized row against fixed ideal points
    (same arithmetic and degenerate rule as :func:`closeness_np`)."""
    import numpy as np
    d_pos = float(np.sqrt(((row - a_pos) ** 2).sum()))
    d_neg = float(np.sqrt(((row - a_neg) ** 2).sum()))
    if d_pos + d_neg <= _EPS:
        return 0.5
    return d_neg / max(d_pos + d_neg, _EPS)


def explain_np(matrix, weights, benefit, valid=None, criteria_names=None):
    """Per-criterion attribution of the winner-vs-runner-up closeness gap.

    Telescoping decomposition: starting from the runner-up's weighted
    normalized row, swap one criterion at a time to the winner's value
    (criteria order) and recompute closeness against the *fixed* ideal
    points of the actual decision. Each swap's closeness delta is that
    criterion's contribution; the deltas sum exactly (up to float
    round-off) to ``cc_winner - cc_runner_up``, so "why did TOPSIS pick
    this node" reads off as C signed numbers. Numpy path only — the
    jax/pallas engines return closeness without the weighted
    intermediates.

    Returns a dict: winner / runner-up indices and closeness, the gap,
    and one ``{criterion, delta_cc, winner_value, runner_up_value}``
    entry per criterion (raw decision-matrix values, not the normalized
    ones). With fewer than two feasible alternatives ``runner_up`` is
    None and ``contributions`` is empty.
    """
    import numpy as np
    matrix = np.asarray(matrix, dtype=np.float64)
    res = closeness_np(matrix, weights, benefit, valid)
    v, a_pos, a_neg, _ = _weighted_and_ideals_np(matrix, weights, benefit,
                                                 valid)
    n_c = matrix.shape[-1]
    if criteria_names is None:
        criteria_names = [f"criterion_{j}" for j in range(n_c)]
    # first max on both picks — the scheduler's argmax tie-break, which
    # res.ranking (unstable argsort) does not guarantee on exact ties
    winner = int(np.argmax(res.closeness))
    feasible = int(np.isfinite(res.closeness).sum())
    if feasible < 2:
        return {"winner": winner, "runner_up": None,
                "closeness_winner": float(res.closeness[winner]),
                "closeness_runner_up": None, "gap": None,
                "contributions": []}
    rest = res.closeness.copy()
    rest[winner] = -np.inf
    runner = int(np.argmax(rest))
    row = v[runner].copy()
    cc_prev = _cc_row_np(row, a_pos, a_neg)
    contributions = []
    for j in range(n_c):
        row[j] = v[winner, j]
        cc_j = _cc_row_np(row, a_pos, a_neg)
        contributions.append({
            "criterion": str(criteria_names[j]),
            "delta_cc": cc_j - cc_prev,
            "winner_value": float(matrix[winner, j]),
            "runner_up_value": float(matrix[runner, j]),
        })
        cc_prev = cc_j
    return {"winner": winner, "runner_up": runner,
            "closeness_winner": float(res.closeness[winner]),
            "closeness_runner_up": float(res.closeness[runner]),
            "gap": float(res.closeness[winner] - res.closeness[runner]),
            "contributions": contributions}
