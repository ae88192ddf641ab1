"""Shared model-building utilities (pure JAX, no flax).

Parameters live in nested dicts of ``jnp`` arrays.  Every module defines its
structure once through a :class:`Builder`, which can run in three modes:

* ``init``  - draw real parameter values from a PRNG key,
* ``axes``  - emit the matching pytree of *logical axis name* tuples,

so parameter values and sharding metadata can never drift apart.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.formats import SparseTensor

PyTree = Any
COMPUTE_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.float32


class Builder:
    """Single-definition parameter structure builder."""

    def __init__(self, mode: str, key: jax.Array | None = None):
        assert mode in ("init", "axes")
        self.mode = mode
        self._key = key
        self._count = 0

    def _next_key(self) -> jax.Array:
        assert self._key is not None, "init mode requires a PRNG key"
        k = jax.random.fold_in(self._key, self._count)
        self._count += 1
        return k

    def child(self) -> "Builder":
        """Independent sub-builder (used for per-stage modules)."""
        if self.mode == "axes":
            return Builder("axes")
        return Builder("init", self._next_key())

    def param(
        self,
        shape: tuple[int, ...],
        axes: tuple[str | None, ...],
        *,
        init: str = "normal",
        scale: float | None = None,
        dtype=PARAM_DTYPE,
    ):
        assert len(shape) == len(axes), (shape, axes)
        if self.mode == "axes":
            # '|'-joined string leaf (tuples would be traversed as pytrees)
            return "|".join(a or "" for a in axes)
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        if init == "ones":
            return jnp.ones(shape, dtype)
        if init == "normal":
            if scale is None:  # fan-in scaling
                fan_in = shape[0] if len(shape) > 1 else shape[-1]
                scale = fan_in ** -0.5
            return (scale * jax.random.truncated_normal(
                self._next_key(), -2.0, 2.0, shape, jnp.float32)).astype(dtype)
        if init == "uniform":
            s = scale if scale is not None else 1.0
            return (s * jax.random.uniform(self._next_key(), shape, jnp.float32, -1.0, 1.0)).astype(dtype)
        raise ValueError(init)


def dense_init(b: Builder, d_in: int, d_out: int, axes: tuple[str | None, str | None],
               *, scale: float | None = None) -> PyTree:
    return {"kernel": b.param((d_in, d_out), axes, scale=scale)}


def dense(params: PyTree, x: jax.Array) -> jax.Array:
    k = params["kernel"]
    if isinstance(k, SparseTensor):
        # 2:4-compressed kernel (sparse.apply.sparsify_params): route through
        # the compressed matmul.  The leaf's kernel_layout tag picks the
        # index path - packed 2-bit planes stream to the Pallas kernel as
        # stored (no host unpack), padded/int8 planes take the fallback.
        # No tape: sparse trees are serving-only.
        from repro.sparse import apply as sparse_apply
        return sparse_apply.sparse_dense(k, x)
    from repro.core import tape as _tape
    t = _tape.current_tape()
    if t is not None:
        t.record(k, x)
    return x @ k.astype(COMPUTE_DTYPE)


def expert_dense(params: PyTree, buf: jax.Array) -> jax.Array:
    """Expert-banked FFN matmul: MoE dispatch buffer (G, E, C, d_in) against
    an (E, d_in, d_out) kernel -> (G, E, C, d_out).

    The expert-bank sibling of :func:`dense`: compressed banks
    (``sparsify_params`` leaves the leading expert axis in the SparseTensor)
    route through the expert-grid ``nm_matmul_expert`` kernel; dense banks
    keep the einsum.  No tape here - ``moe_apply`` records the dispatch
    buffer itself, with routed-token counts.
    """
    k = params["kernel"]
    if isinstance(k, SparseTensor):
        from repro.sparse import apply as sparse_apply
        return sparse_apply.sparse_moe_dense(k, buf)
    return jnp.einsum("gecd,edf->gecf", buf, k.astype(COMPUTE_DTYPE))


def expert_dense_pair(p_up: PyTree, p_gate: PyTree, buf: jax.Array
                      ) -> tuple[jax.Array, jax.Array]:
    """Fused up+gate expert-bank pair sharing the reduction dim.

    When both banks are compressed AND carry matching K-shard tags, the two
    expert-grid kernels run under one shard_map with a single deferred psum
    (one collective for the whole MoE projection group); otherwise falls
    back to two independent :func:`expert_dense` calls, preserving the
    dense-einsum and untagged-compressed paths bit-for-bit.
    """
    ku, kg = p_up["kernel"], p_gate["kernel"]
    if isinstance(ku, SparseTensor) and isinstance(kg, SparseTensor):
        from repro.kernels.shard import pair_k_sharded
        if pair_k_sharded(ku, kg):
            from repro.sparse import apply as sparse_apply
            return sparse_apply.sparse_moe_dense2(ku, kg, buf)
    return expert_dense(p_up, buf), expert_dense(p_gate, buf)


def kernel_dense(params: PyTree) -> jax.Array:
    """Dense view of a (possibly compressed) kernel param, for the few call
    sites that read weights directly (e.g. MLA absorbed-matmul decode)."""
    k = params["kernel"]
    return k.to_dense() if isinstance(k, SparseTensor) else k


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(b: Builder, dim: int) -> PyTree:
    return {"scale": b.param((dim,), ("embed_act",), init="zeros")}


def rmsnorm(params: PyTree, x: jax.Array, *, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    # gemma-style (1 + scale) so zeros-init is identity
    return (x * (1.0 + params["scale"].astype(jnp.float32))).astype(dt)


def layernorm_init(b: Builder, dim: int) -> PyTree:
    return {"scale": b.param((dim,), ("embed_act",), init="zeros"),
            "bias": b.param((dim,), ("embed_act",), init="zeros")}


def layernorm(params: PyTree, x: jax.Array, *, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"]) + params["bias"]).astype(dt)


# ---------------------------------------------------------------------------
# Positional encodings / misc ops
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, *, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding. x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freq  # (..., seq, half)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


def sinusoidal_positions(num: int, dim: int) -> np.ndarray:
    pos = np.arange(num)[:, None]
    i = np.arange(dim // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / dim)
    out = np.zeros((num, dim), np.float32)
    out[:, 0::2] = np.sin(angle)
    out[:, 1::2] = np.cos(angle)
    return out


def embed_init(b: Builder, vocab: int, dim: int) -> PyTree:
    return {"table": b.param((vocab, dim), ("vocab", "embed"), scale=1.0)}


def embed_lookup(params: PyTree, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["table"].astype(COMPUTE_DTYPE), tokens, axis=0)


def unembed(params: PyTree, x: jax.Array) -> jax.Array:
    """Tied unembedding: x @ table.T -> logits (fp32)."""
    table = params["table"].astype(COMPUTE_DTYPE)
    return jnp.einsum("...d,vd->...v", x, table).astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class ShapeDtype:
    shape: tuple[int, ...]
    dtype: Any

    def struct(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, self.dtype)
