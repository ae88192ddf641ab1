"""Fused UniPruning inner loop: local metric + dual update + Gamma prox.

The search stage touches every prunable parameter every step with a pure
elementwise chain (score -> V update -> soft-threshold).  Unfused, XLA
materializes S and reads/writes each operand separately: ~5 reads + 3 writes
of W-sized tensors per step.  This kernel does it in one HBM pass:
reads W, Gamma, V (+ per-row stats), writes V', Gamma'.

Metric selection is static:
  wanda:      S = |W| * a[:, None]
  ria/stoch:  S = (|W|/rowsum + |W|/colsum) * sqrt(a)[:, None]
  magnitude:  S = |W|

a / rowsum enter as (K, 1) blocks, colsum as (1, N) - all VMEM-resident per
tile; the tile shape (bk x bn) is VPU-lane aligned (multiples of 8 x 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _fuse_kernel(w_ref, a_ref, row_ref, col_ref, g_ref, v_ref,
                 vout_ref, gout_ref, *, v_lr, lam, metric):
    w = jnp.abs(w_ref[...].astype(jnp.float32))
    if metric == "wanda":
        s = w * a_ref[...].astype(jnp.float32)
    elif metric == "magnitude":
        s = w
    else:  # ria / stochria
        a = jnp.sqrt(jnp.maximum(a_ref[...].astype(jnp.float32), 1e-12))
        s = (w / (row_ref[...].astype(jnp.float32) + 1e-12)
             + w / (col_ref[...].astype(jnp.float32) + 1e-12)) * a
    v_new = v_ref[...].astype(jnp.float32) - \
        v_lr * (g_ref[...].astype(jnp.float32) - s)
    vout_ref[...] = v_new
    gout_ref[...] = jnp.sign(v_new) * jnp.maximum(jnp.abs(v_new) - lam, 0.0)


@functools.partial(jax.jit, static_argnames=("metric", "v_lr", "lam", "bk",
                                             "bn", "interpret"))
def saliency_fused_step(w, a, gamma, v, *, metric: str = "wanda",
                        v_lr: float = 0.1, lam: float = 1e-3,
                        rowsum=None, colsum=None, bk: int = 256,
                        bn: int = 512, interpret: bool = False):
    """Returns (V', Gamma'). w: (K, N); a: (K,); rowsum: (K,); colsum: (N,)."""
    K, N = w.shape
    bk = min(bk, K)
    bn = min(bn, N)
    assert K % bk == 0 and N % bn == 0
    a2 = a.reshape(K, 1).astype(jnp.float32)
    row2 = (rowsum if rowsum is not None
            else jnp.ones((K,), jnp.float32)).reshape(K, 1)
    col2 = (colsum if colsum is not None
            else jnp.ones((N,), jnp.float32)).reshape(1, N)
    grid = (K // bk, N // bn)
    return pl.pallas_call(
        functools.partial(_fuse_kernel, v_lr=v_lr, lam=lam, metric=metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),   # w
            pl.BlockSpec((bk, 1), lambda i, j: (i, 0)),    # a
            pl.BlockSpec((bk, 1), lambda i, j: (i, 0)),    # rowsum
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),    # colsum
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),   # gamma
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),   # v
        ],
        out_specs=[
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((K, N), jnp.float32),
                   jax.ShapeDtypeStruct((K, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(w, a2, row2, col2, gamma, v)
