"""Split-KV decode attention Pallas kernel (flash-decoding style).

One query token vs a long KV cache is pure HBM streaming: arithmetic
intensity ~ 2 flops/byte, far below the v5e ridge (~240).  The kernel tiles
the KV capacity dim, keeps a running (m, l, acc) softmax state per KV head
in VMEM scratch, and writes the normalized output on the final chunk - one
pass over KV, no (C,)-sized logits materialized in HBM.

Masking comes in as an additive bias vector (0 / -inf per slot), computed
once outside from ring positions - so the same kernel serves dense, ring
(sliding-window) and sequence-sharded caches (the partial (m, l, acc)
combine across shards is decode_attend's psum path).

Grid: (B, C/bc), last dim arbitrary (sequential accumulation).  Blocks keep
the TPU (8, 128) rule by taking whole trailing dims: a KV block is
(1, bc, K, D) - every KV head of bc cache rows, read per head inside the
kernel - the query block is (1, K, G, D) and the bias rides as (B, 1, C)
so its block is (1, 1, bc).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _stream_chunk(q_ref, k_ref, v_ref, bias_ref, m_ref, l_ref, acc_ref, *,
                  scale):
    """Fold one (bc)-row KV chunk into every head's running softmax."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bias = bias_ref[0].astype(jnp.float32)          # (1, bc)
    for h in range(k_ref.shape[2]):
        q = q_ref[0, h].astype(jnp.float32)         # (G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)   # (bc, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)   # (bc, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale + bias                        # (G, bc)
        m_prev = m_ref[h]                           # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _decode_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, nc, scale):
    _stream_chunk(q_ref, k_ref, v_ref, bias_ref, m_ref, l_ref, acc_ref,
                  scale=scale)

    @pl.when(pl.program_id(1) == nc - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _call(kernel, q, k, v, bias, *, out_specs, out_shape, bc, interpret):
    """Shared pallas_call plumbing of the two decode kernels."""
    B, K, G, D = q.shape
    C = k.shape[1]
    Dv = v.shape[-1]
    bc = min(bc, C)
    assert C % bc == 0, (C, bc)
    return pl.pallas_call(
        kernel,
        grid=(B, C // bc),
        in_specs=[
            pl.BlockSpec((1, K, G, D), lambda b, c: (b, 0, 0, 0)),
            pl.BlockSpec((1, bc, K, D), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, bc, K, Dv), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, bc), lambda b, c: (b, 0, c)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((K, G, 1), jnp.float32),
                        pltpu.VMEM((K, G, 1), jnp.float32),
                        pltpu.VMEM((K, G, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, bias[:, None, :])


@functools.partial(jax.jit, static_argnames=("scale", "bc", "interpret"))
def flash_decode(q, k, v, bias, *, scale=None, bc: int = 512,
                 interpret: bool = False):
    """q: (B, K, G, D); k/v: (B, C, K, D/Dv); bias: (B, C) additive mask.

    Returns (B, K, G, Dv).
    """
    B, K, G, D = q.shape
    Dv = v.shape[-1]
    scale = D ** -0.5 if scale is None else scale
    bc = min(bc, k.shape[1])
    return _call(
        functools.partial(_decode_kernel, nc=k.shape[1] // bc, scale=scale),
        q, k, v, bias,
        out_specs=pl.BlockSpec((1, K, G, Dv), lambda b, c: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, Dv), q.dtype),
        bc=bc, interpret=interpret)


def flash_decode_ref(q, k, v, bias, *, scale=None):
    """Materialized oracle."""
    B, K, G, D = q.shape
    scale = D ** -0.5 if scale is None else scale
    s = jnp.einsum("bkgd,bckd->bkgc", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = s + bias[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgc,bckd->bkgd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# Partial (un-normalized) variant for capacity-sharded caches
# ---------------------------------------------------------------------------

def _decode_partial_kernel(q_ref, k_ref, v_ref, bias_ref, acc_o, m_o, l_o,
                           m_ref, l_ref, acc_ref, *, nc, scale):
    """Same streaming state as ``_decode_kernel`` but the flush emits the raw
    (acc, m, l) instead of acc/l - the caller combines partials across
    capacity shards (pmax on m, psum on rescaled l/acc) before normalizing
    once.  An all-masked shard flushes m = -1e30, whose cross-shard
    correction exp(m - m_global) zeroes its partial exactly."""
    _stream_chunk(q_ref, k_ref, v_ref, bias_ref, m_ref, l_ref, acc_ref,
                  scale=scale)

    @pl.when(pl.program_id(1) == nc - 1)
    def _flush():
        acc_o[0] = acc_ref[...]
        m_o[0] = m_ref[...]
        l_o[0] = l_ref[...]


@functools.partial(jax.jit, static_argnames=("scale", "bc", "interpret"))
def flash_decode_partial(q, k, v, bias, *, scale=None, bc: int = 512,
                         interpret: bool = False):
    """Un-normalized flash decode over (a shard of) the KV capacity.

    Same operands as :func:`flash_decode`; returns float32
    ``(acc (B, K, G, Dv), m (B, K, G, 1), l (B, K, G, 1))`` with
    ``acc = sum_c exp(s_c - m) v_c`` and ``l = sum_c exp(s_c - m)`` - the
    running softmax state, flushed raw so shard partials combine exactly
    like the kernel's own chunk accumulation, just across devices.
    """
    B, K, G, D = q.shape
    Dv = v.shape[-1]
    scale = D ** -0.5 if scale is None else scale
    bc = min(bc, k.shape[1])
    spec = lambda w: pl.BlockSpec((1, K, G, w), lambda b, c: (b, 0, 0, 0))
    return _call(
        functools.partial(_decode_partial_kernel, nc=k.shape[1] // bc,
                          scale=scale),
        q, k, v, bias,
        out_specs=[spec(Dv), spec(1), spec(1)],
        out_shape=[jax.ShapeDtypeStruct((B, K, G, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, K, G, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, K, G, 1), jnp.float32)],
        bc=bc, interpret=interpret)


def flash_decode_partial_ref(q, k, v, bias, *, scale=None):
    """Materialized (acc, m, l) oracle for the partial kernel."""
    B, K, G, D = q.shape
    scale = D ** -0.5 if scale is None else scale
    s = jnp.einsum("bkgd,bckd->bkgc", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = s + bias[:, None, None, :]
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), -1e30)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bkgc,bckd->bkgd", p, v.astype(jnp.float32))
    return acc, m, l
