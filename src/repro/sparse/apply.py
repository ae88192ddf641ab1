"""Sparse execution: route SparseTensor kernels through ``nm_matmul``.

``models.common.dense`` dispatches on leaf type, so a params tree whose
prunable kernels were replaced by :func:`sparsify_params` serves through the
compressed kernel (Pallas on TPU, a decompress-and-dot reference on CPU)
while every dense leaf keeps the existing path.  MoE expert banks
(E, d_in, d_out) dispatch the same way through ``models.common.expert_dense`` ->
:func:`sparse_moe_dense`, which consumes the dispatch buffer (G, E, C, d)
directly against the expert-grid kernel ``nm_matmul_expert``.  The leaf's ``kernel_layout`` tag decides what
the kernel streams: 2-bit-packed index planes (K % 8 == 0) go to the kernel
*as stored* - the unpack happens inside the kernel after the HBM->VMEM copy,
so there is no host-side ``unpacked_idx()`` round-trip on the serving path.
Byte-padded planes (K % 8 != 0) and int8 storage take the int8 fallback.
On CPU the GEMM decompresses and runs one dense dot, which keeps the
accumulation order identical to XLA's dense bf16 dot - sparse serving
reproduces masked-dense serving token-for-token.  The branch is chosen when
the program is lowered for its platform (``jax.lax.platform_dependent``);
any platform but CPU or TPU is an error.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.nm_spmm import (LAYOUT_PACKED2, nm_matmul,
                                   nm_matmul_expert, unpack_idx2)
from repro.sparse import pack as pack_mod
from repro.sparse.formats import SparseTensor

PyTree = Any


def _largest_block(dim: int, cap: int, mult: int = 1) -> int:
    """Largest b <= cap with dim % b == 0 and b % mult == 0.

    mult encodes the TPU tiling preference (lane dim = multiples of 128,
    reduction tiles = multiples of 4 for the 2:4 groups); callers drop the
    preference when the dim itself cannot satisfy it.
    """
    for b in range(min(cap, dim), mult - 1, -1):
        if dim % b == 0 and b % mult == 0:
            return b
    return dim  # dim < mult: single block


def _nm_reference(x: jax.Array, vals: jax.Array, idx: jax.Array,
                  layout: str, out_dtype=None) -> jax.Array:
    """CPU execution: decompress and run one dense dot per GEMM.

    The dense weight is exact (2:4 placement of the stored values), and one
    fp32-accumulated dot keeps XLA's dense contraction order, so sparse
    serving on the CPU reproduces masked-dense serving token-for-token.
    """
    from repro.kernels import ref
    if layout == LAYOUT_PACKED2:
        idx = unpack_idx2(idx)
    decompress = ref.decompress_24
    for _ in range(vals.ndim - 2):
        decompress = jax.vmap(decompress)
    w = decompress(vals, idx).astype(x.dtype)
    if x.ndim == 2:
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    else:
        y = jnp.einsum("emk,ekn->emn", x, w,
                       preferred_element_type=jnp.float32)
    return y.astype(out_dtype or x.dtype)


def _run_nm(x: jax.Array, vals: jax.Array, idx: jax.Array, layout: str,
            kernel=nm_matmul, out_dtype=None) -> jax.Array:
    """x (M, K) through ``nm_matmul`` or, with ``kernel=nm_matmul_expert``,
    a per-expert batch (E, M, K) through the expert-grid kernel.

    ``jax.lax.platform_dependent`` picks the branch when the program is
    lowered: the Pallas kernel for a TPU, :func:`_nm_reference` for the
    CPU, and an error for any other platform.  Kernel blocks follow the TPU
    (8, 128) rule: each block dim is the whole array dim or a multiple of
    the tile.  An M above 128 that 8 does not divide is zero-padded to the
    next multiple of 8 and the pad rows dropped.
    """
    def on_tpu(x, vals, idx):
        m, k = x.shape[-2:]
        n = vals.shape[-1]
        pad = -m % 8 if m > 128 else 0
        if pad:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)])
        bm = m if m <= 128 else _largest_block(m + pad, 128, 8)
        # vals tiles are (bk/2, bn) and packed index tiles (bk/8, bn): bk a
        # multiple of 256 keeps both on the tiling, else the whole K
        bk = _largest_block(k, 512, 256) if k % 256 == 0 else k
        bn = _largest_block(n, 256, 128) if n % 128 == 0 else n
        y = kernel(x, vals, idx, bm=bm, bk=bk, bn=bn, layout=layout,
                   out_dtype=out_dtype)
        return y[..., :m, :] if pad else y

    def on_cpu(x, vals, idx):
        return _nm_reference(x, vals, idx, layout, out_dtype)

    return jax.lax.platform_dependent(x, vals, idx, cpu=on_cpu, tpu=on_tpu)


def _kernel_operand(st: SparseTensor) -> tuple[jax.Array, str]:
    """Index plane + layout tag as the kernel consumes it.

    Kernel-native packed storage ships the stored bytes untouched; padded
    or int8 storage unpacks to the int8 fallback plane at dispatch.
    """
    layout = st.kernel_layout
    if layout == LAYOUT_PACKED2:
        return st.idx, layout
    return st.unpacked_idx(), layout


def _tp(st: SparseTensor) -> bool:
    """Route through the shard-mapped kernels?  True when the leaf carries
    a tensor-parallel tag (``dist.sharding.tag_compressed``) and rules are
    installed at trace time (``serve.engine.EngineFns(rules=...)``)."""
    from repro.kernels.shard import tp_routed
    return tp_routed(st)


def sparse_dense(st: SparseTensor, x: jax.Array) -> jax.Array:
    """x: (..., K) @ compressed (K, N) -> (..., N) in x.dtype."""
    assert len(st.vals.shape) == 2, (
        "per-layer kernels only; stacked leaves are sliced by lax.scan")
    *lead, k = x.shape
    x2 = x.reshape(-1, k)
    if _tp(st):
        from repro.kernels import shard as ksh
        y = ksh.nm_dense_sharded(st, x2, site=st.shard_site)
        return y.reshape(*lead, st.shape[-1])
    idx, layout = _kernel_operand(st)
    y = _run_nm(x2, st.vals.astype(x.dtype), idx, layout)
    return y.reshape(*lead, st.shape[-1])


def sparse_moe_dense(st: SparseTensor, buf: jax.Array) -> jax.Array:
    """MoE dispatch buffer (G, E, C, d) @ compressed expert bank (E, d, N)
    -> (G, E, C, N) in buf.dtype.

    Consumes the dispatch buffer directly: tokens regroup per expert to
    (E, G*C, d) and run through ``nm_matmul_expert`` - one kernel invocation
    covers every expert's GEMM, replacing ``moe_apply``'s masked-dense
    einsum.  The index plane ships exactly as :func:`_kernel_operand`
    decides for 2-D kernels (packed 2-bit when K % 8 == 0, int8 fallback
    otherwise).
    """
    assert st.ndim == 3, (
        "expert banks are (E, K, N); stacked (layers, E, K, N) leaves are "
        "sliced by lax.scan before reaching the kernel")
    G, E, C, d = buf.shape
    assert st.shape[0] == E and st.shape[1] == d, (st.shape, buf.shape)
    x3 = buf.swapaxes(0, 1).reshape(E, G * C, d)
    if _tp(st):
        from repro.kernels import shard as ksh
        y = ksh.nm_moe_sharded(st, x3, site=st.shard_site)
        return y.reshape(E, G, C, st.shape[-1]).swapaxes(0, 1)
    idx, layout = _kernel_operand(st)
    y = _run_nm(x3, st.vals.astype(buf.dtype), idx, layout,
                kernel=nm_matmul_expert)
    return y.reshape(E, G, C, st.shape[-1]).swapaxes(0, 1)


def sparse_dense2(st_a: SparseTensor, st_b: SparseTensor, x: jax.Array
                  ) -> tuple[jax.Array, jax.Array]:
    """Fused pair sharing the reduction dim (gated-MLP up+gate).

    A K-shard-tagged pair (``kernels.shard.pair_k_sharded``) runs two local
    kernels under one shard_map with ONE deferred variadic psum for the
    whole projection group; any other pair is two :func:`sparse_dense`
    calls (a pre-concat of vals/idx would re-copy the weights every step).
    """
    from repro.kernels import shard as ksh
    if not ksh.pair_k_sharded(st_a, st_b):
        return sparse_dense(st_a, x), sparse_dense(st_b, x)
    *lead, k = x.shape
    ya, yb = ksh.nm_dense2_sharded(st_a, st_b, x.reshape(-1, k),
                                   site=st_a.shard_site)
    return (ya.reshape(*lead, st_a.shape[-1]),
            yb.reshape(*lead, st_b.shape[-1]))


def sparse_moe_dense2(st_up: SparseTensor, st_gate: SparseTensor,
                      buf: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused up+gate expert banks over one dispatch buffer (K-shard-tagged
    pair only): two local expert-grid kernels, one deferred psum across the
    pair and the expert grid.  Callers check
    ``kernels.shard.pair_k_sharded`` first."""
    from repro.kernels import shard as ksh
    G, E, C, d = buf.shape
    x3 = buf.swapaxes(0, 1).reshape(E, G * C, d)
    h, g = ksh.nm_moe2_sharded(st_up, st_gate, x3, site=st_up.shard_site)
    return (h.reshape(E, G, C, st_up.shape[-1]).swapaxes(0, 1),
            g.reshape(E, G, C, st_gate.shape[-1]).swapaxes(0, 1))


# ---------------------------------------------------------------------------
# Tree conversion
# ---------------------------------------------------------------------------

def _stacked(axes_str: str | None) -> bool:
    return bool(axes_str) and axes_str.startswith("layers|")


def _aligned_leaves(ref_flat, ref_treedef, tree: PyTree, name: str) -> list:
    """Flatten ``tree`` and validate it is structure-identical to params.

    A silently mis-paired zip here would compress kernels against the wrong
    masks (or worse, truncate the iteration); mismatches raise with the
    first offending key path instead.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: x is None)
    if treedef != ref_treedef:
        ref_paths = [jax.tree_util.keystr(kp) for kp, _ in ref_flat]
        got_paths = [jax.tree_util.keystr(kp) for kp, _ in flat]
        for rp, gp in zip(ref_paths, got_paths):
            if rp != gp:
                raise ValueError(
                    f"{name} tree does not match params: first offending "
                    f"key path {gp!r} ({name}) vs {rp!r} (params)")
        if len(ref_paths) != len(got_paths):
            longer, which = ((ref_paths, "params") if len(ref_paths)
                             > len(got_paths) else (got_paths, name))
            raise ValueError(
                f"{name} tree does not match params: {len(got_paths)} "
                f"leaves vs {len(ref_paths)} params leaves; first unmatched "
                f"key path "
                f"{longer[min(len(ref_paths), len(got_paths))]!r} ({which})")
        # every key path matches: the trees differ only in container types
        raise ValueError(
            f"{name} tree does not match params: same {len(ref_paths)} leaf "
            f"paths but different container structure "
            f"({treedef} vs params {ref_treedef})")
    return [leaf for _, leaf in flat]


def _is_expert_bank(path: str, eff_ndim: int) -> bool:
    """3-D-per-layer-step MoE expert bank (E, d_in, d_out)?

    The leading dim must be an expert axis the consumer
    (``moe_apply`` -> :func:`sparse_moe_dense`) dispatches over - keyed on
    the ``['moe']`` subtree so unrelated 3-D kernels (e.g. per-head
    recurrent weights) never get a layout their call sites cannot execute.
    """
    return eff_ndim == 3 and "['moe']" in path


def sparsify_params(params: PyTree, masks: PyTree, *, axes: PyTree = None,
                    idx_bits: int = 2, dtype=None,
                    predicate: Callable[[str], bool] | None = None) -> PyTree:
    """Replace 2:4-maskable kernels with SparseTensor leaves; mask the rest.

    masks: keep-mask pytree from ``mirror.export_masks`` (mode="nm").  A
    kernel is compressed when its mask is 2:4-valid along the reduction dim
    and it is, per layer step, either 2-D or a 3-D MoE expert bank
    (E, d_in, d_out) (``axes`` - the ``models.model.param_axes`` tree -
    identifies scan-stacked leaves, whose leading "layers" axis is sliced by
    ``lax.scan`` before execution).  Non-compressible masked leaves get
    ``W * mask``; None-mask leaves pass through untouched.

    masks/axes must be structure-identical to params: a mismatched tree
    raises with the first offending key path instead of silently truncating
    the zip and pairing kernels with the wrong masks.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    flat_m = _aligned_leaves(flat, treedef, masks, "masks")
    flat_a = (_aligned_leaves(flat, treedef, axes, "axes")
              if axes is not None else [None] * len(flat))
    out = []
    for (kp, w), mk, ax in zip(flat, flat_m, flat_a, strict=True):
        if mk is None:
            out.append(w)
            continue
        path = jax.tree_util.keystr(kp)
        eff_ndim = w.ndim - (1 if _stacked(ax) else 0)
        k_dim = w.shape[-2]
        compressible = ((eff_ndim == 2 or _is_expert_bank(path, eff_ndim))
                        and k_dim % 4 == 0
                        and (predicate is None or predicate(path))
                        and _is_nm(mk))
        if compressible:
            # k_dim % 8 != 0 no longer widens to int8: the packed plane is
            # zero-padded to the byte boundary instead (the kernel takes the
            # int8 fallback there, but storage keeps the 2-bit byte win)
            out.append(pack_mod.pack_nm(w, mk, idx_bits=idx_bits,
                                        dtype=dtype))
        else:
            out.append(w * mk.astype(w.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def shared_leaves(params0: PyTree, tree: PyTree) -> int:
    """How many of ``tree``'s leaves are ``params0``'s buffers, unchanged.

    Pruning replaces only the pruned kernels (SparseTensor or ``W * mask``);
    every None-mask leaf - embeddings, norms, biases - must pass through by
    object identity, so N budget variants built from one ``params0`` share
    ONE copy of the untouched leaves instead of N.  This is the fleet's
    memory-sharing invariant; SparseTensor leaves are new storage by
    definition and never count.
    """
    ids = {id(leaf) for leaf in jax.tree.leaves(params0)}
    return sum(
        id(leaf) in ids
        for leaf in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, SparseTensor))
        if not isinstance(leaf, SparseTensor))


def _is_nm(mask: jax.Array, m: int = 4, n: int = 2) -> bool:
    """Host-side check: exactly n kept per contiguous group of m."""
    if mask.shape[-2] % m:
        return False
    g = np.asarray(mask).reshape(*mask.shape[:-2], mask.shape[-2] // m, m,
                                 mask.shape[-1])
    return bool((g.sum(-2) == n).all())


def compressed_report(params: PyTree, masks: PyTree = None) -> dict:
    """Per-leaf and total weight bytes: compressed vs dense-bf16 equivalent.

    ``layout`` is the storage layout tag; ``kernel_layout`` is what the
    matmul actually streams (a byte-padded packed plane executes through the
    int8 fallback), so the bytes accounting stays honest: ``nbytes`` counts
    the stored (padded) plane, never a phantom unpadded one.

    With ``masks`` (the keep-mask tree the params were sparsified against),
    pruned leaves that did NOT compress - masked-dense fallbacks serving the
    full dense byte footprint - are reported too, with
    ``bytes_compressed == bytes_dense_bf16``, ``kernel_layout ==
    "masked-dense"`` and ``fallback: True``, and they count into the
    headline ratio; without masks only SparseTensor leaves are visible and
    the ratio covers compressed leaves alone.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, SparseTensor))
    flat_m = (_aligned_leaves(flat, treedef, masks, "masks")
              if masks is not None else [None] * len(flat))
    layers = []
    for (kp, leaf), mk in zip(flat, flat_m, strict=True):
        if isinstance(leaf, SparseTensor):
            d = 1
            for s in leaf.shape:
                d *= s
            d *= 2  # bf16 serving layout
            layers.append({"path": jax.tree_util.keystr(kp),
                           "shape": list(leaf.shape),
                           "idx_bits": leaf.idx_bits,
                           "layout": leaf.layout,
                           "kernel_layout": leaf.kernel_layout,
                           "bytes_compressed": leaf.nbytes,
                           "bytes_dense_bf16": d,
                           "ratio": leaf.nbytes / d,
                           "fallback": False})
        elif mk is not None:
            # pruned but served masked-dense: full dense bytes move
            d = 2 * int(np.prod(leaf.shape))
            layers.append({"path": jax.tree_util.keystr(kp),
                           "shape": list(leaf.shape), "idx_bits": None,
                           "layout": None, "kernel_layout": "masked-dense",
                           "bytes_compressed": d, "bytes_dense_bf16": d,
                           "ratio": 1.0, "fallback": True})
    comp = sum(r["bytes_compressed"] for r in layers)
    dense_eq = sum(r["bytes_dense_bf16"] for r in layers)
    kernel_native = sum(r["kernel_layout"] == LAYOUT_PACKED2 for r in layers)
    return {"layers": layers, "bytes_compressed": comp,
            "bytes_dense_bf16": dense_eq,
            "kernel_native_packed": kernel_native,
            "fallback_leaves": sum(r["fallback"] for r in layers),
            "ratio": comp / dense_eq if dense_eq else None}
