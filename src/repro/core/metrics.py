"""Local saliency metrics S(W, X).

All metrics operate on a kernel W of shape (*lead, d_in, d_out) with optional
activation stats a of shape (*lead, d_in) = per-input-feature RMS norm over
the calibration set.  When a is None they gracefully degrade to their
weight-only form (magnitude).

  magnitude : |W|                                     (Zhu & Gupta 2017)
  wanda     : |W| * a[..., None]                      (Sun et al. 2024)
  ria       : (|W|/rowsum + |W|/colsum) * a^0.5       (Zhang et al. 2024)
  stochria  : RIA with subsampled row/col sums        (Yi & Richtarik 2025)

These are differentiable in W (abs subgradient), which the mirror-descent
alignment term relies on.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

METRICS = ("magnitude", "wanda", "ria", "stochria")


def magnitude(w: jax.Array, a=None, *, key=None) -> jax.Array:
    return jnp.abs(w.astype(jnp.float32))


def wanda(w: jax.Array, a=None, *, key=None) -> jax.Array:
    s = jnp.abs(w.astype(jnp.float32))
    if a is not None:
        s = s * a[..., None]
    return s


def _ria_core(w, a, row_w=None, col_w=None, eps=1e-12):
    aw = jnp.abs(w.astype(jnp.float32))
    # rowsum: over d_out for each input row; colsum: over d_in per output col
    if row_w is None:
        rowsum = jnp.sum(aw, axis=-1, keepdims=True)
        colsum = jnp.sum(aw, axis=-2, keepdims=True)
    else:
        rowsum = jnp.sum(aw * row_w, axis=-1, keepdims=True) / \
            jnp.mean(row_w)
        colsum = jnp.sum(aw * col_w, axis=-2, keepdims=True) / \
            jnp.mean(col_w)
    s = aw / (rowsum + eps) + aw / (colsum + eps)
    if a is not None:
        s = s * jnp.sqrt(jnp.maximum(a, 1e-12))[..., None]
    return s


def ria(w: jax.Array, a=None, *, key=None) -> jax.Array:
    return _ria_core(w, a)


def stochria(w: jax.Array, a=None, *, key=None, frac: float = 0.9) -> jax.Array:
    """RIA with Bernoulli-subsampled row/col sums (stochastic normalizers)."""
    if key is None:
        return _ria_core(w, a)
    k1, k2 = jax.random.split(key)
    row_w = jax.random.bernoulli(k1, frac, w.shape[-1:]).astype(jnp.float32)
    col_w = jax.random.bernoulli(k2, frac, w.shape[-2:-1]).astype(jnp.float32)
    return _ria_core(w, a, row_w=row_w, col_w=col_w[..., :, None])


def get_metric(name: str, stoch_frac: float = 0.9):
    if name == "magnitude":
        return magnitude
    if name == "wanda":
        return wanda
    if name == "ria":
        return ria
    if name == "stochria":
        return partial(stochria, frac=stoch_frac)
    raise ValueError(f"unknown metric {name!r}; options: {METRICS}")


def normalize_scores(s: jax.Array, how: str) -> jax.Array:
    """Per-tensor scale normalization: makes saliency cross-layer comparable
    so ONE global budget can redistribute sparsity across layers (the
    paper's 'global controller'; see DESIGN.md #8 and EXPERIMENTS.md)."""
    if how == "none":
        return s
    # The normalizer is a per-tensor scale CONSTANT (not part of the
    # saliency geometry): stop_gradient keeps the alignment gradient on the
    # scores themselves and avoids differentiating through sort.
    if how == "mean":
        return s / (jax.lax.stop_gradient(jnp.mean(s)) + 1e-12)
    if how == "median":
        flat = jax.lax.stop_gradient(s.reshape(-1))
        med = kth_smallest_nonneg(flat, flat.size // 2)
        return s / (med + 1e-12)
    raise ValueError(how)


def kth_smallest_nonneg(x: jax.Array, k: int) -> jax.Array:
    """Exactly ``jnp.sort(x)[k]`` for a flat non-negative float32 ``x``.

    Bisection on the bit pattern, which orders non-negative floats like
    the values: 31 rounds of a count that reduces like a sum, so a sharded
    ``x`` is never gathered.  A sort of a stacked (layers, K, N) score leaf
    copies it whole onto every device and does not fit the four-chip
    full-depth search.  A -0.0 counts as the smallest value (its bits are
    negative as int32) and comes back as +0.0, which compares equal.  Where
    the device compares subnormals as zero (CPU and TPU do), the sort may
    return a subnormal where this returns +0.0: equal as the device
    compares them.  The count is int32, so ``x`` must hold fewer than 2**31
    elements.
    """
    if x.size >= 2 ** 31:
        raise ValueError(
            f"kth_smallest_nonneg counts in int32: {x.size} elements do "
            "not fit (split the leaf before normalizing)")
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)

    def halve(_, lo_hi):
        lo, hi = lo_hi
        mid = lo + (hi - lo) // 2
        enough = jnp.sum(bits <= mid, dtype=jnp.int32) > k
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    # invariant: the answer's bits lie in [lo, hi]; +inf bounds from above
    lo, _ = jax.lax.fori_loop(0, 31, halve,
                              (jnp.int32(0), jnp.int32(0x7F800000)))
    return jax.lax.bitcast_convert_type(lo, jnp.float32)


def metric_tree(name: str, params: Any, stats: Any, prunable: Any,
                key: jax.Array | None = None, stoch_frac: float = 0.9,
                norm: str = "none") -> Any:
    """Apply the metric leafwise over prunable kernels; None elsewhere."""
    fn = get_metric(name, stoch_frac)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    flat_stats, _ = jax.tree_util.tree_flatten(
        stats, is_leaf=lambda x: x is None)
    flat_pr, _ = jax.tree_util.tree_flatten(prunable)
    # stats now come from two implementations (jitted pass / eager tape) and
    # from persisted bank artifacts: refuse silent leaf misalignment.
    if len(flat_stats) != len(leaves) or len(flat_pr) != len(leaves):
        raise ValueError(
            f"metric_tree leaf mismatch: params={len(leaves)} "
            f"stats={len(flat_stats)} prunable={len(flat_pr)} leaves - the "
            "stats/prunable trees must mirror the params structure")
    out = []
    for i, (w, a, pr) in enumerate(zip(leaves, flat_stats, flat_pr)):
        if not pr:
            out.append(None)
            continue
        k = None if key is None else jax.random.fold_in(key, i)
        out.append(normalize_scores(fn(w, a, key=k), norm))
    return jax.tree_util.tree_unflatten(treedef, out)
