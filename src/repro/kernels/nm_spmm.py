"""2:4 structured-sparse matmul Pallas kernel (TPU adaptation of the paper's
NVIDIA-sparse-tensor-core speedup, Table 8).

TPU MXUs have no sparse mode, so the win is HBM *bandwidth*: decode-shape
GEMMs are memory-bound (arithmetic intensity ~ batch << 240 flops/byte), and
a 2:4 weight stored compressed moves ~9/16 of the dense bf16 bytes
(values K/2*N*2B + 2-bit packed indices K/8*N*1B vs dense K*N*2B; int8
indices give the weaker 3/4 fallback).  The kernel streams compressed tiles
HBM->VMEM and never builds the dense tile: Mosaic refuses the row
interleave that would need (8-bit iota, 3-D (g, 4, bn) relayouts).  The
expansion moves to the activation side instead: ``spread_x`` lays x out as
four (M, K/2) planes, plane q holding the dense column each compressed row
stands for when its position is q, and the kernel accumulates
sum_q xq[q] @ (vals where idx == q) - four 2-D dots over the (bk/2, bn)
value tile, every operand 32-bit or bf16.

Layout: W (K, N) pruned 2:4 along K (the reduction dim).  Compressed:
  vals (K/2, N)  bf16   - the two surviving values per group of 4
and one of two index layouts, named by the tags in ``sparse.formats``:
  idx  (K/2, N)  int8   - LAYOUT_INT8: in-group positions (0..3), ascending
  idx  (K/8, N)  uint8  - LAYOUT_PACKED2: 4 positions per byte, bits 2j..2j+1
                          hold the position of compressed row 4r+j

With LAYOUT_PACKED2 the packed bytes are what streams HBM->VMEM; the 2-bit
unpack (a 0/1 row-repeat matmul, then a per-row shift/mask) runs *after*
the copy, so the index plane costs K/8*N bytes of bandwidth instead of
K/2*N.  The int8 path is
kept as a fallback (byte-padded planes, legacy callers).

Block tiling: (4 x bm x bk/2) activation planes against compressed operand
tiles (bk/2 x bn) vals and (bk/2 x bn | bk/8 x bn) idx; K is the innermost
(arbitrary) grid dim accumulating into an f32 VMEM scratch, flushed to the
output on the last K step.

MoE expert banks (E, K, N) pruned 2:4 along K use ``nm_matmul_expert``: the
same compressed tiles gain a leading expert axis and the grid a leading
(parallel) expert dimension, so per-expert GEMMs over the dispatch buffer
stream each expert's 9/16 bytes without a masked-dense fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The index-plane layout tags the kernel dispatches on.  Single source of
# truth; ``sparse.formats`` re-exports them for the storage side.
LAYOUT_INT8 = "int8"
LAYOUT_PACKED2 = "packed2"


def unpack_idx2(packed: jax.Array) -> jax.Array:
    """(..., rows, n) uint8 packed codes -> (..., rows*4, n) int8 positions.

    The storage-side definition of the 2-bit layout: byte row r carries
    compressed rows 4r..4r+3 in bit pairs 2j..2j+1.  Runs in XLA (host /
    ``sparse.formats`` unpack); the kernel unpacks the same bytes with the
    2-D formulation in :func:`_tile_codes`, checked against this one in the
    tests.
    """
    *lead, rows, n = packed.shape
    p = packed.astype(jnp.int32)
    codes = [(p >> (2 * j)) & 0x3 for j in range(4)]
    out = jnp.stack(codes, axis=-2)                # (..., rows, 4, n)
    return out.reshape(*lead, rows * 4, n).astype(jnp.int8)


def _tile_codes(idx, packed: bool, rows: int):
    """Index tile -> (rows, bn) int32 in-group positions, 2-D and 32-bit.

    int8 planes already hold one position per compressed row.  A packed
    (rows/4, bn) tile is widened to one byte per compressed row with a 0/1
    row-repeat matmul on the MXU (bytes 0..255 are exact in bf16 and the
    f32 accumulator), then each row shifts out its own bit pair: row c
    reads bits 2*(c % 4).  No 8-bit iota and no 3-D reshape, which Mosaic
    refuses.
    """
    if not packed:
        return idx.astype(jnp.int32)
    nb = idx.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, nb), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, nb), 1)
    repeat = jnp.where((r >> 2) == c, 1.0, 0.0).astype(jnp.bfloat16)
    byte = idx.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)
    byte = jnp.dot(repeat, byte,
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    shift = 2 * (jax.lax.broadcasted_iota(jnp.int32, byte.shape, 0) & 3)
    return (byte >> shift) & 3


def _accumulate(acc_ref, xq, vals, codes) -> None:
    """acc += sum_q xq[q] @ (vals where codes == q).

    ``xq[q]`` (bm, bk/2) holds the activation column each compressed row
    meets when its position is q (see :func:`spread_x`), so the 2:4
    expansion happens on the activation side and every operand stays a
    plain 2-D tile.
    """
    vf = vals.astype(jnp.float32)
    acc = acc_ref[...]
    for q in range(4):
        wq = jnp.where(codes == q, vf, 0.0).astype(xq.dtype)
        acc += jnp.dot(xq[q], wq, preferred_element_type=jnp.float32)
    acc_ref[...] = acc


def spread_x(x: jax.Array) -> jax.Array:
    """(..., M, K) -> (..., 4, M, K/2): plane q, column c holds
    x[..., 4 * (c // 2) + q] - the dense row compressed row c stands for
    when its in-group position is q."""
    *lead, m, k = x.shape
    x4 = x.reshape(*lead, m, k // 4, 1, 4)
    x4 = jnp.broadcast_to(x4, (*lead, m, k // 4, 2, 4))
    return jnp.moveaxis(x4.reshape(*lead, m, k // 2, 4), -1, -3)


def _nm_matmul_kernel(xq_ref, vals_ref, idx_ref, o_ref, acc_ref, *, nk,
                      packed):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    vals = vals_ref[...]
    codes = _tile_codes(idx_ref[...], packed, vals.shape[0])
    _accumulate(acc_ref, xq_ref[...], vals, codes)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _infer_layout(K: int, idx_shape: tuple[int, ...]) -> str:
    if idx_shape[-2] * 2 == K:
        return LAYOUT_INT8
    if idx_shape[-2] * 8 == K:
        return LAYOUT_PACKED2
    raise ValueError(f"index plane {idx_shape} matches no layout for K={K}")


def infer_layout(K: int, idx_shape: tuple[int, ...]) -> str:
    """Index-plane layout from shapes alone (K/2 rows -> int8, K/8 ->
    packed2).

    Works on *shard-local* shapes too: under ``shard_map`` each device holds
    (K_loc/2, N) vals and (K_loc/2 | K_loc/8, N) idx slices of the same
    layout, and the row ratio is sharding-invariant, so the per-device
    kernel call infers the layout from its local operands with no global
    metadata.
    """
    return _infer_layout(K, idx_shape)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "bn", "layout", "interpret",
                                    "out_dtype"))
def nm_matmul(x: jax.Array, vals: jax.Array, idx: jax.Array, *,
              bm: int = 128, bk: int = 512, bn: int = 256,
              layout: str | None = None,
              interpret: bool = False, out_dtype=None) -> jax.Array:
    """x: (M, K) @ 2:4-compressed W (K, N) -> (M, N) in x.dtype.

    layout: LAYOUT_INT8 (idx (K/2, N) int8) or LAYOUT_PACKED2 (idx (K/8, N)
    uint8, consumed packed - no host-side unpack); None infers from shapes.

    out_dtype: output dtype override (default x.dtype).  The tensor-parallel
    wrappers pass float32 so K-partial results leave the kernel as the raw
    f32 accumulator and the cross-device psum adds full-precision partials
    before the single cast back to the activation dtype.
    """
    M, K = x.shape
    halfK, N = vals.shape
    assert halfK * 2 == K, (x.shape, vals.shape)
    layout = _infer_layout(K, idx.shape) if layout is None else layout
    packed = layout == LAYOUT_PACKED2
    if packed:
        assert K % 8 == 0 and idx.shape == (K // 8, N), (idx.shape, K, N)
    else:
        assert layout == LAYOUT_INT8 and idx.shape == (halfK, N), \
            (layout, idx.shape)
    bm = min(bm, M)
    bk = min(bk, K)
    bn = min(bn, N)
    idx_rows = 8 if packed else 2
    # int8 tiles need whole 2:4 groups (bk % 4); packed tiles additionally
    # need whole index bytes (bk % 8)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0 \
        and bk % (8 if packed else 4) == 0
    nk = K // bk
    return pl.pallas_call(
        functools.partial(_nm_matmul_kernel, nk=nk, packed=packed),
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((4, bm, bk // 2), lambda m, n, k: (0, m, k)),
            pl.BlockSpec((bk // 2, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((bk // idx_rows, bn), lambda m, n, k: (k, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(spread_x(x), vals, idx)


# ---------------------------------------------------------------------------
# Expert-banked variant (MoE)
# ---------------------------------------------------------------------------

def _nm_matmul_expert_kernel(xq_ref, vals_ref, idx_ref, o_ref, acc_ref, *,
                             nk, packed):
    """Same tile math as ``_nm_matmul_kernel``; the grid grew a leading
    expert dim so every ref carries a size-1 expert block (sliced off with
    [0]).  One (bm x bn) f32 accumulator per (e, m, n) program."""
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    vals = vals_ref[0]
    codes = _tile_codes(idx_ref[0], packed, vals.shape[0])
    _accumulate(acc_ref, xq_ref[0], vals, codes)

    @pl.when(pl.program_id(3) == nk - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bk", "bn", "layout", "interpret",
                                    "out_dtype"))
def nm_matmul_expert(x: jax.Array, vals: jax.Array, idx: jax.Array, *,
                     bm: int = 128, bk: int = 512, bn: int = 256,
                     layout: str | None = None,
                     interpret: bool = False, out_dtype=None) -> jax.Array:
    """Per-expert batch x: (E, M, K) @ 2:4-compressed bank (E, K, N)
    -> (E, M, N) in x.dtype.

    The compressed operands carry a leading expert axis - vals (E, K/2, N),
    idx (E, K/2, N) int8 | (E, K/8, N) uint8 - and the grid grows a leading
    (parallel) expert dimension, so each program streams one expert's
    compressed tiles HBM->VMEM and runs the same VMEM shift/mask unpack +
    in-register expand as the 2-D kernel.  MoE dispatch buffers (G, E, C, d)
    reshape to (E, G*C, d) and route through here (see
    ``sparse.apply.sparse_moe_dense``).
    """
    E, M, K = x.shape
    Ev, halfK, N = vals.shape
    assert Ev == E and halfK * 2 == K, (x.shape, vals.shape)
    layout = _infer_layout(K, idx.shape) if layout is None else layout
    packed = layout == LAYOUT_PACKED2
    if packed:
        assert K % 8 == 0 and idx.shape == (E, K // 8, N), (idx.shape, K, N)
    else:
        assert layout == LAYOUT_INT8 and idx.shape == (E, halfK, N), \
            (layout, idx.shape)
    bm = min(bm, M)
    bk = min(bk, K)
    bn = min(bn, N)
    idx_rows = 8 if packed else 2
    assert M % bm == 0 and K % bk == 0 and N % bn == 0 \
        and bk % (8 if packed else 4) == 0
    nk = K // bk
    return pl.pallas_call(
        functools.partial(_nm_matmul_expert_kernel, nk=nk, packed=packed),
        grid=(E, M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((1, 4, bm, bk // 2),
                         lambda e, m, n, k: (e, 0, m, k)),
            pl.BlockSpec((1, bk // 2, bn), lambda e, m, n, k: (e, k, n)),
            pl.BlockSpec((1, bk // idx_rows, bn),
                         lambda e, m, n, k: (e, k, n)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda e, m, n, k: (e, m, n)),
        out_shape=jax.ShapeDtypeStruct((E, M, N), out_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(spread_x(x), vals, idx)
