"""Group-of-4 kernels: the R_{2:4} proximal operator and 2:4 mask extraction.

Both are local to contiguous groups of 4 along the K (reduction) dim -
perfect VPU work with zero cross-lane traffic.  Tiles are (bk x bn) with
bk % 4 == 0; groups are processed as a (bk/4, 4, bn) view in-register.

prox: damped Jacobi fixed point on u_i = max(0, |w_i| - lam * e2_i(u_others))
      (Kuebler et al. 2501.18015), signs restored - runs every search step in
      N:M mode, so it shares the fused-pass motivation of saliency_fuse.
mask: top-2 |s| per group -> bool mask, deterministic tie-break by position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _prox_kernel(w_ref, o_ref, *, lam, iters, damping):
    w = w_ref[...].astype(jnp.float32)
    bk, bn = w.shape
    g = w.reshape(bk // 4, 4, bn)
    absw = jnp.abs(g)
    u = absw
    for _ in range(iters):
        u0, u1, u2, u3 = u[:, 0], u[:, 1], u[:, 2], u[:, 3]
        e0 = u1 * u2 + u2 * u3 + u3 * u1
        e1 = u0 * u2 + u2 * u3 + u3 * u0
        e2 = u0 * u1 + u1 * u3 + u3 * u0
        e3 = u0 * u1 + u1 * u2 + u2 * u0
        grad = jnp.stack([e0, e1, e2, e3], axis=1)
        u = damping * jnp.maximum(absw - lam * grad, 0.0) + \
            (1 - damping) * u
    out = jnp.sign(g) * u
    o_ref[...] = out.reshape(bk, bn).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("lam", "iters", "damping", "bk",
                                             "bn", "interpret"))
def prox24(w: jax.Array, *, lam: float, iters: int = 12,
           damping: float = 0.7, bk: int = 256, bn: int = 512,
           interpret: bool = False) -> jax.Array:
    K, N = w.shape
    bk = min(bk, K)
    bn = min(bn, N)
    assert K % bk == 0 and N % bn == 0 and bk % 4 == 0
    return pl.pallas_call(
        functools.partial(_prox_kernel, lam=lam, iters=iters,
                          damping=damping),
        grid=(K // bk, N // bn),
        in_specs=[pl.BlockSpec((bk, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((K, N), w.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(w)


def _mask_kernel(s_ref, o_ref):
    s = jnp.abs(s_ref[...].astype(jnp.float32))
    bk, bn = s.shape
    g = s.reshape(bk // 4, 4, bn)
    # rank of element i = #{j: g_j > g_i, or g_j == g_i with j earlier}
    gi = g[:, :, None, :]   # axis 1 = i
    gj = g[:, None, :, :]   # axis 2 = j
    pos = jnp.arange(4)
    j_earlier = pos[None, None, :, None] < pos[None, :, None, None]
    beats = (gj > gi) | ((gj == gi) & j_earlier)
    rank = jnp.sum(beats, axis=2)
    mask = rank < 2
    o_ref[...] = mask.reshape(bk, bn)


@functools.partial(jax.jit, static_argnames=("bk", "bn", "interpret"))
def nm_mask24(s: jax.Array, *, bk: int = 256, bn: int = 512,
              interpret: bool = False) -> jax.Array:
    """Top-2-of-4 keep-mask along K. s: (K, N) scores -> bool (K, N)."""
    K, N = s.shape
    bk = min(bk, K)
    bn = min(bn, N)
    assert K % bk == 0 and N % bn == 0 and bk % 4 == 0
    return pl.pallas_call(
        _mask_kernel,
        grid=(K // bk, N // bn),
        in_specs=[pl.BlockSpec((bk, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bk, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((K, N), jnp.bool_),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(s)
