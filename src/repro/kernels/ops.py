"""Public wrappers around the Pallas kernels: padding, layout, backend
dispatch (compiled on TPU, interpret mode on CPU, an error elsewhere — see
:func:`resolve_interpret`), and shape restoration.

These are the entry points the rest of the framework uses; each has a
pure-jnp oracle in repro.kernels.ref and a sweep test in tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topsis as _topsis
from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm_pallas as _rn
from repro.kernels import topsis_pallas as _tp

_EPS = 1e-12


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas call runs in the interpreter: an explicit
    ``interpret`` wins; otherwise the default backend decides — ``cpu``
    interprets (the tests and CPU rehearsals), ``tpu`` compiles the kernels
    with Mosaic, and any other platform raises rather than fall back to
    the interpreter unannounced."""
    if interpret is not None:
        return interpret
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default JAX backend is {platform!r}")


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# --- TOPSIS -----------------------------------------------------------------
def _auto_block_n(n: int) -> int:
    return min(_tp.DEFAULT_BLOCK_N,
               max(_tp.LANE, 2 ** int(np.ceil(np.log2(max(n, 1))))))


def topsis_closeness(matrix: jax.Array, weights: jax.Array,
                     benefit: jax.Array, *, valid: jax.Array | None = None,
                     block_n: int | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """Closeness coefficients for (N, C) decision matrix; C <= 8 (both the
    paper's 5-criteria matrix and the carbon-extended 6-criteria one fit
    the kernel's C_PAD=8 sublane padding — padded criteria rows carry zero
    weight and contribute nothing).

    Global reductions (column norms, ideal points) run in XLA; the O(N*C)
    distance/closeness hot loop runs in the Pallas kernel. ``valid`` is an
    optional (N,) feasibility mask: invalid rows are excluded from the ideal
    points and returned as -inf (never rank first) — identical semantics to
    ``repro.core.topsis.closeness``.
    """
    interpret = resolve_interpret(interpret)
    n, c = matrix.shape
    assert c <= _tp.C_PAD, f"at most {_tp.C_PAD} criteria, got {c}"
    benefit = jnp.asarray(benefit, bool)
    if valid is not None:
        valid = jnp.asarray(valid, bool)
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), _EPS)
    mat = jnp.asarray(matrix).astype(jnp.float32)
    norms = jnp.sqrt(jnp.sum(mat * mat, axis=0))
    inv_norm = 1.0 / jnp.maximum(norms, _EPS)
    v = mat * inv_norm * w
    a_pos, a_neg = _topsis.masked_ideal_points(v, benefit, valid)

    if block_n is None:
        block_n = _auto_block_n(n)
    xt = _pad_to(_pad_to(mat.T, 0, _tp.C_PAD), 1, block_n)

    def col(x):  # (C,) -> (C_PAD, 1)
        return _pad_to(x.astype(jnp.float32)[:, None], 0, _tp.C_PAD)

    cc = _tp.topsis_closeness_blocks(xt, col(inv_norm), col(w), col(a_pos),
                                     col(a_neg), block_n=block_n,
                                     interpret=interpret)
    cc = cc[0, :n]
    if valid is not None:
        cc = jnp.where(valid, cc, -jnp.inf)
    return cc


def topsis_closeness_grid(mats: jax.Array, weights: jax.Array,
                          benefit: jax.Array, *,
                          valid: jax.Array | None = None,
                          block_n: int | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """(S, P, N) closeness for a (P, N, C) queue tensor under an (S, C)
    weight-scheme grid; C <= 8. The Pareto-sweep batch path: column norms
    are scheme-independent and computed once per pod, the per-(scheme, pod)
    ideal points are global reductions in XLA, and the Pallas kernel walks
    the (pods x node blocks x schemes) grid with schemes innermost so each
    criteria node-block is fetched from HBM once and reused across all S
    schemes (see ``topsis_pallas.topsis_closeness_grid_blocks``). ``valid``
    is the usual (P, N) feasibility mask, shared by every scheme; row
    semantics match ``repro.core.topsis.closeness_grid_np``.
    """
    interpret = resolve_interpret(interpret)
    mats = jnp.asarray(mats).astype(jnp.float32)
    p, n, c = mats.shape
    assert c <= _tp.C_PAD, f"at most {_tp.C_PAD} criteria, got {c}"
    benefit = jnp.asarray(benefit, bool)
    if valid is not None:
        valid = jnp.asarray(valid, bool)
    ws = jnp.asarray(weights, jnp.float32)
    assert ws.ndim == 2 and ws.shape[-1] == c, (ws.shape, mats.shape)
    s = ws.shape[0]
    ws = ws / jnp.maximum(jnp.sum(ws, axis=-1, keepdims=True), _EPS)
    norms = jnp.sqrt(jnp.sum(mats * mats, axis=1))              # (P, C)
    inv_norm = 1.0 / jnp.maximum(norms, _EPS)
    # (S, P, N, C) weighted normalized tensor — only for the ideal-point
    # reductions; the kernel recomputes v blockwise from the (P, N, C) data
    v = mats[None] * inv_norm[None, :, None, :] * ws[:, None, None, :]
    a_pos, a_neg = _topsis.masked_ideal_points(
        v, benefit, None if valid is None else valid[None])     # (S, P, C)

    if block_n is None:
        block_n = _auto_block_n(n)
    xt = _pad_to(_pad_to(mats.transpose(0, 2, 1), 1, _tp.C_PAD), 2, block_n)

    def col_p(x):   # (P, C) -> (P, C_PAD, 1)
        return _pad_to(x.astype(jnp.float32), 1, _tp.C_PAD)[:, :, None]

    def col_sp(x):  # (S, P, C) -> (S, P, C_PAD, 1)
        return _pad_to(x.astype(jnp.float32), 2, _tp.C_PAD)[:, :, :, None]

    wsp = jnp.broadcast_to(ws[:, None, :], (s, p, c))
    cc = _tp.topsis_closeness_grid_blocks(
        xt, col_p(inv_norm), col_sp(wsp), col_sp(a_pos), col_sp(a_neg),
        block_n=block_n, interpret=interpret)
    cc = cc[:, :, 0, :n]
    if valid is not None:
        cc = jnp.where(valid[None], cc, -jnp.inf)
    return cc


def topsis_closeness_kinds(mats_kinds: jax.Array, kind_idx: jax.Array,
                           weights: jax.Array, benefit: jax.Array, *,
                           valid: jax.Array | None = None,
                           block_n: int | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """(P, N) closeness from a deduplicated (K, N, C) kind tensor plus a
    (P,) pod->kind index; C <= 8. The incremental batch path: the fleet
    criteria cache keeps one matrix per workload *kind* (K is small — the
    paper's workload mix has three), so the kernel streams K criteria
    tensors instead of P near-duplicate pod copies.

    Per-pod column norms are gathered from per-kind norms — bitwise equal
    to the per-pod reduction because each pod's rows ARE its kind's rows.
    Ideal points stay per pod (``valid`` differs pod to pod) and run in
    XLA; ``weights`` is (C,) shared or (P, C) per pod; ``valid`` is an
    optional (P, N) feasibility mask (excluded from the ideals, -inf in
    the result, as in :func:`topsis_closeness`). With ``kind_idx =
    arange(P)`` it scores a plain (P, N, C) queue tensor.
    """
    interpret = resolve_interpret(interpret)
    mats_kinds = jnp.asarray(mats_kinds).astype(jnp.float32)
    k, n, c = mats_kinds.shape
    kind_idx = jnp.asarray(kind_idx, jnp.int32)
    p = kind_idx.shape[0]
    assert c <= _tp.C_PAD, f"at most {_tp.C_PAD} criteria, got {c}"
    benefit = jnp.asarray(benefit, bool)
    if valid is not None:
        valid = jnp.asarray(valid, bool)
    w = jnp.asarray(weights, jnp.float32)
    if w.ndim == 1:
        w = jnp.broadcast_to(w, (p, c))
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), _EPS)
    knorms = jnp.sqrt(jnp.sum(mats_kinds * mats_kinds, axis=1))   # (K, C)
    inv_norm = (1.0 / jnp.maximum(knorms, _EPS))[kind_idx]        # (P, C)
    v = mats_kinds[kind_idx] * inv_norm[:, None, :] * w[:, None, :]
    a_pos, a_neg = _topsis.masked_ideal_points(v, benefit, valid)  # (P, C)

    if block_n is None:
        block_n = _auto_block_n(n)
    xt = _pad_to(_pad_to(mats_kinds.transpose(0, 2, 1), 1, _tp.C_PAD),
                 2, block_n)

    def col(x):  # (P, C) -> (P, C_PAD, 1)
        return _pad_to(x.astype(jnp.float32), 1, _tp.C_PAD)[:, :, None]

    cc = _tp.topsis_closeness_kinds_blocks(
        kind_idx, xt, col(inv_norm), col(w), col(a_pos), col(a_neg),
        block_n=block_n, interpret=interpret)
    cc = cc[:, 0, :n]
    if valid is not None:
        cc = jnp.where(valid, cc, -jnp.inf)
    return cc


# --- RMSNorm ----------------------------------------------------------------
def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6, *,
            block_rows: int = 256, interpret: bool | None = None) -> jax.Array:
    """Fused RMSNorm over the last axis of x (any leading shape)."""
    interpret = resolve_interpret(interpret)
    d = x.shape[-1]
    lead = x.shape[:-1]
    rows = int(np.prod(lead)) if lead else 1
    x2d = _pad_to(_pad_to(x.reshape(rows, d), 1, 128), 0, block_rows)
    g2d = _pad_to(gamma.reshape(1, d), 1, 128)
    out = _rn.rmsnorm_blocks(x2d, g2d, eps=eps, d_true=d,
                             block_rows=min(block_rows, x2d.shape[0]),
                             interpret=interpret)
    return out[:rows, :d].reshape(*lead, d)


# --- Flash attention ----------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, causal, window, sm_scale, bq, bk, kv_len,
                interpret):
    out, _ = _fa.flash_attention_blocks(
        q, k, v, sm_scale=sm_scale, causal=causal, window=window,
        bq=bq, bk=bk, kv_len=kv_len, interpret=interpret)
    return out


def _flash_core_fwd(q, k, v, causal, window, sm_scale, bq, bk, kv_len,
                    interpret):
    out, lse = _fa.flash_attention_blocks(
        q, k, v, sm_scale=sm_scale, causal=causal, window=window,
        bq=bq, bk=bk, kv_len=kv_len, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, window, sm_scale, bq, bk, kv_len, interpret,
                    res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _fa.flash_attention_bwd_blocks(
        q, k, v, out, lse, do, sm_scale=sm_scale, causal=causal,
        window=window, bq=bq, bk=bk, kv_len=kv_len, interpret=interpret)
    return dq, dk, dv


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    sm_scale: float | None = None, bq: int = 128,
                    bk: int = 128, interpret: bool | None = None) -> jax.Array:
    """(B, H, S, D) GQA flash attention; pads S to block multiples and D to
    the 128-lane boundary. Differentiable: backward runs the flash backward
    Pallas kernels (dq + fused dk/dv), not a rematerialized-score fallback."""
    interpret = resolve_interpret(interpret)
    b, h, sq, d = q.shape
    skv = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    bq = min(bq, max(8, 1 << (sq - 1).bit_length()))
    bk = min(bk, max(8, 1 << (skv - 1).bit_length()))
    qp = _pad_to(_pad_to(q, 2, bq), 3, 128)
    kp = _pad_to(_pad_to(k, 2, bk), 3, 128)
    vp = _pad_to(_pad_to(v, 2, bk), 3, 128)
    out = _flash_core(qp, kp, vp, causal, window, sm_scale, bq, bk, skv,
                      interpret)
    return out[:, :, :sq, :d]
