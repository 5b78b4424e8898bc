"""Pallas TPU kernel for fleet-scale TOPSIS batch scoring.

At 1000+ node scale the scheduler scores N candidate slices x C criteria for
every arriving job; the hot loop is the weighted-normalize + two Euclidean
distances + closeness. This kernel tiles alternatives along the TPU lane axis
(layout (C_pad, N): criteria on sublanes, alternatives on lanes) so the
distance reduction is a cheap sublane reduction, and streams N in VMEM-sized
blocks.

Column norms and the ideal/anti-ideal rows are global O(N*C) reductions,
computed once in the wrapper (repro.kernels.ops.topsis_closeness) — the
kernel consumes them as small VMEM-resident operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
C_PAD = 8          # criteria padded to one sublane group
DEFAULT_BLOCK_N = 2048

_EPS = 1e-12


def _topsis_kernel(xt_ref, inv_norm_ref, w_ref, a_pos_ref, a_neg_ref, cc_ref):
    """One block: xt (C_PAD, BLOCK_N) raw criteria (transposed);
    inv_norm/w/a_pos/a_neg (C_PAD, 1); out cc (1, BLOCK_N).

    Padded criteria rows carry zeros in w and a_pos/a_neg, so they
    contribute nothing to the distances.
    """
    xt = xt_ref[...].astype(jnp.float32)
    v = xt * inv_norm_ref[...] * w_ref[...]            # weighted normalized
    dp = v - a_pos_ref[...]
    dn = v - a_neg_ref[...]
    d_pos = jnp.sqrt(jnp.sum(dp * dp, axis=0, keepdims=True))
    d_neg = jnp.sqrt(jnp.sum(dn * dn, axis=0, keepdims=True))
    denom = d_pos + d_neg
    cc = d_neg / jnp.maximum(denom, _EPS)
    cc_ref[...] = jnp.where(denom <= _EPS, 0.5, cc)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def topsis_closeness_blocks(xt: jax.Array, inv_norm: jax.Array, w: jax.Array,
                            a_pos: jax.Array, a_neg: jax.Array,
                            block_n: int = DEFAULT_BLOCK_N,
                            interpret: bool = False) -> jax.Array:
    """xt: (C_PAD, N_pad) with N_pad % block_n == 0; small operands (C_PAD, 1).
    Returns (1, N_pad) closeness coefficients."""
    c_pad, n_pad = xt.shape
    assert c_pad == C_PAD and n_pad % block_n == 0, (xt.shape, block_n)
    grid = (n_pad // block_n,)
    small = pl.BlockSpec((C_PAD, 1), lambda i: (0, 0))
    return pl.pallas_call(
        _topsis_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((C_PAD, block_n), lambda i: (0, i)),
            small, small, small, small,
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        interpret=interpret,
    )(xt, inv_norm, w, a_pos, a_neg)


def _topsis_grid_kernel(xt_ref, inv_norm_ref, w_ref, a_pos_ref, a_neg_ref,
                        cc_ref):
    """One (pod, node-block, scheme) grid cell of the weight-grid form:
    xt (1, C_PAD, BLOCK_N) raw criteria for pod p — scheme-independent, so
    its BlockSpec index map ignores the scheme coordinate and the pipeline
    keeps the block resident across all S schemes; per-(scheme, pod) small
    operands (1, 1, C_PAD, 1); out cc (1, 1, 1, BLOCK_N). Math is
    :func:`_topsis_kernel` with the pod axis leading (the criteria
    reduction is over axis 1) and the scheme block-dim stripped."""
    xt = xt_ref[...].astype(jnp.float32)
    v = xt * inv_norm_ref[...] * w_ref[0]
    dp = v - a_pos_ref[0]
    dn = v - a_neg_ref[0]
    d_pos = jnp.sqrt(jnp.sum(dp * dp, axis=1, keepdims=True))
    d_neg = jnp.sqrt(jnp.sum(dn * dn, axis=1, keepdims=True))
    denom = d_pos + d_neg
    cc = d_neg / jnp.maximum(denom, _EPS)
    cc_ref[...] = jnp.where(denom <= _EPS, 0.5, cc)[None]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def topsis_closeness_grid_blocks(xt: jax.Array, inv_norm: jax.Array,
                                 w: jax.Array, a_pos: jax.Array,
                                 a_neg: jax.Array,
                                 block_n: int = DEFAULT_BLOCK_N,
                                 interpret: bool = False) -> jax.Array:
    """Weight-scheme-grid scoring: xt (P, C_PAD, N_pad) raw criteria shared
    by every scheme; per-pod inv_norm (P, C_PAD, 1); per-(scheme, pod)
    w / a_pos / a_neg (S, P, C_PAD, 1). The grid is (pods, node blocks,
    schemes) with the scheme axis INNERMOST (fastest-varying): Pallas only
    re-fetches an operand block when its index-map output changes between
    consecutive grid steps, and xt's map ignores the scheme coordinate — so
    each (pod, node-block) criteria tile is pulled from HBM once and reused
    across all S schemes, keeping criteria traffic at O(P*N) rather than
    O(S*P*N). Schemes lead the OUTPUT layout instead: returns
    (S, P, 1, N_pad) closeness, one contiguous (P, N) plane per scheme."""
    p, c_pad, n_pad = xt.shape
    s = w.shape[0]
    assert c_pad == C_PAD and n_pad % block_n == 0, (xt.shape, block_n)
    assert w.shape == a_pos.shape == a_neg.shape == (s, p, C_PAD, 1), (
        w.shape, a_pos.shape, a_neg.shape)
    grid = (p, n_pad // block_n, s)
    small = pl.BlockSpec((1, 1, C_PAD, 1), lambda b, i, k: (k, b, 0, 0))
    return pl.pallas_call(
        _topsis_grid_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, C_PAD, block_n), lambda b, i, k: (b, 0, i)),
            pl.BlockSpec((1, C_PAD, 1), lambda b, i, k: (b, 0, 0)),
            small, small, small,
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_n),
                               lambda b, i, k: (k, b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((s, p, 1, n_pad), jnp.float32),
        interpret=interpret,
    )(xt, inv_norm, w, a_pos, a_neg)


def _topsis_kinds_kernel(kind_ref, xt_ref, inv_norm_ref, w_ref, a_pos_ref,
                         a_neg_ref, cc_ref):
    """One (pod, node-block) grid cell of the kind-indexed form: the
    scalar-prefetch ``kind_ref`` steered this pod's criteria block — the
    BlockSpec index map reads ``kind_ref[b]`` — so ``xt_ref`` holds the
    (1, C_PAD, BLOCK_N) block of the pod's *workload kind*, not a per-pod
    copy. Math is :func:`_topsis_kernel` with the pod axis leading (the
    criteria reduction is over axis 1); the small operands stay per pod
    (each pod's feasibility mask shapes its ideal points even when the raw
    criteria rows are shared)."""
    del kind_ref       # consumed by the index maps, not the kernel body
    xt = xt_ref[...].astype(jnp.float32)
    v = xt * inv_norm_ref[...] * w_ref[...]
    dp = v - a_pos_ref[...]
    dn = v - a_neg_ref[...]
    d_pos = jnp.sqrt(jnp.sum(dp * dp, axis=1, keepdims=True))
    d_neg = jnp.sqrt(jnp.sum(dn * dn, axis=1, keepdims=True))
    denom = d_pos + d_neg
    cc = d_neg / jnp.maximum(denom, _EPS)
    cc_ref[...] = jnp.where(denom <= _EPS, 0.5, cc)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def topsis_closeness_kinds_blocks(kind_idx: jax.Array, xt: jax.Array,
                                  inv_norm: jax.Array, w: jax.Array,
                                  a_pos: jax.Array, a_neg: jax.Array,
                                  block_n: int = DEFAULT_BLOCK_N,
                                  interpret: bool = False) -> jax.Array:
    """Kind-indexed whole-queue scoring: xt (K, C_PAD, N_pad) holds one
    criteria tensor per *workload kind* (K << P), ``kind_idx`` (P,) int32
    maps each pod to its kind row, and per-pod small operands stay
    (P, C_PAD, 1). The grid is still (pods, node blocks), but the kernel
    streams each kind's blocks from HBM instead of P near-duplicate pod
    copies — the bandwidth saving that lets the batch path scale past the
    (P, N, C) materialization ceiling. Returns (P, 1, N_pad)."""
    k, c_pad, n_pad = xt.shape
    p = kind_idx.shape[0]
    assert c_pad == C_PAD and n_pad % block_n == 0, (xt.shape, block_n)
    grid = (p, n_pad // block_n)
    small = pl.BlockSpec((1, C_PAD, 1), lambda b, i, kind_ref: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, C_PAD, block_n),
                         lambda b, i, kind_ref: (kind_ref[b], 0, i)),
            small, small, small, small,
        ],
        out_specs=pl.BlockSpec((1, 1, block_n),
                               lambda b, i, kind_ref: (b, 0, i)),
    )
    return pl.pallas_call(
        _topsis_kinds_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((p, 1, n_pad), jnp.float32),
        interpret=interpret,
    )(kind_idx, xt, inv_norm, w, a_pos, a_neg)
