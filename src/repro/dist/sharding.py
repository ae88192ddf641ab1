"""Sharding derivation: params / batch / KV-cache NamedSharding trees.

Specs are derived from the logical-axis annotations the model emits
(``models.model.param_axes``) through a :class:`~repro.dist.axes.ShardingRules`
mapping, with a per-dimension divisibility fallback (a dim that the mapped
mesh axes do not divide is replicated instead of erroring).

Compressed leaves (``sparse.formats.SparseTensor`` / ``BitMask``) shard too,
and the K (contraction) dim is FIRST-CLASS: a SparseTensor standing in for
a dense (K, N) kernel inherits the dense kernel's logical axes, and its K
sharding is decided once for the *leaf* - both components shard K iff the
shard-local slices stay kernel-executable, i.e. K % (8 * devices) == 0 for
2-bit-packed planes (whole index bytes per shard) resp. K % (4 * devices)
== 0 for int8 planes (whole 2:4 groups).  A leaf that cannot honor its K
rule replicates BOTH components along K and says so loudly
(``obs.log(warn=...)`` with the leaf path and axis) instead of the old
silent per-component divisibility fallback, which could leave ``vals``
K-sharded with a replicated ``idx`` - a layout no kernel executes.
K-shardable leaves additionally get a static ``shard`` tag
(:func:`tag_compressed`) that routes dispatch through the shard-mapped
kernels in ``kernels/shard.py`` (explicit K-partial accumulation).  Expert-
banked leaves ((E, K, N) per layer step, possibly under a leading "layers"
scan axis) carry the expert dim through unchanged.  BitMask bits are a
flat byte buffer with no meaningful axis: replicated.

``REPRO_FORCE_REPLICATED=1`` forces the replicated-K fallback everywhere
(no tags stamped, specs keep K unsharded) - the escape hatch for bisecting
mesh/collective bugs.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import keystr, tree_map_with_path

from repro import obs
from repro.dist.axes import ShardingRules, make_rules, spec_for_shape
from repro.sparse.formats import BitMask, SparseTensor

PyTree = Any


def make_production_rules(mesh, *, seq_shard_kv: Any = False,
                          seq_parallel: bool = False) -> ShardingRules:
    """Rules for the production mesh (pod/data FSDP + model TP)."""
    return make_rules(mesh, seq_parallel=seq_parallel,
                      seq_shard_kv=seq_shard_kv)


def _data_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _one(axes):
    return axes[0] if isinstance(axes, tuple) and len(axes) == 1 else axes


def _axes_size(mesh, entry) -> int:
    if entry is None:
        return 1
    n = 1
    for a in ((entry,) if isinstance(entry, str) else tuple(entry)):
        n *= mesh.shape[a]
    return n


def _site_for(path: str) -> str:
    """Projection-group label for collective accounting, from the leaf path."""
    if "['moe']" in path:
        return "moe"
    if "['attn']" in path:
        return "attn"
    if "['mlp']" in path or "['shared']" in path:
        return "mlp"
    return "dense"


def sparse_component_layout(axes_str: str | None, st: SparseTensor,
                            rules: ShardingRules, *, path: str = "",
                            quiet: bool = False):
    """One compressed leaf -> (vals_spec, idx_spec, shard_tag).

    The single source of the K-sharding decision, shared by
    :func:`sparse_leaf_sharding` (the NamedSharding tree) and
    :func:`tag_compressed` (the dispatch tag) so placement and execution can
    never disagree.  K shards iff ``K % (group * devices) == 0`` with group
    8 (2-bit-packed planes: whole index bytes per shard; a byte-padded
    plane has K % 8 != 0 and never qualifies) resp. 4 (int8 planes: whole
    2:4 groups per shard); otherwise BOTH components replicate K and a
    structured warning names the leaf and axis (suppressed with ``quiet``,
    and entirely under ``REPRO_FORCE_REPLICATED``).  Leading dims (layers /
    experts) and N keep the dense per-dim divisibility fallback.  The tag
    is ``(site, *entries)`` over the *executed* dims (leading "layers"
    stripped - lax.scan slices it away before dispatch) and is None unless
    K actually shards or, K replicated, an executed N or expert dim does:
    the K entry is then None (no psum), and the tag still routes the leaf
    through a shard_map - on a TPU a Pallas kernel cannot run under the
    partitioner's automatic sharding.
    """
    from repro.kernels.shard import replicated_forced
    mesh = rules.mesh
    if axes_str is None:
        return P(), P(), None
    names = axes_str.split("|")
    shape = st.shape
    dense_spec = tuple(rules.spec(names))
    entries = list(dense_spec) + [None] * (len(shape) - len(dense_spec))
    lead = []
    for i, e in enumerate(entries[:-2]):
        sz = _axes_size(mesh, e)
        lead.append(e if sz <= 1 or shape[i] % sz == 0 else None)
    K, N = shape[-2], shape[-1]
    k_e, n_e = entries[-2], entries[-1]
    n_keep = n_e if N % _axes_size(mesh, n_e) == 0 else None
    d = _axes_size(mesh, k_e)
    forced = replicated_forced()
    group = 8 if st.idx_bits == 2 else 4
    k_tag = None
    spec_k = k_e
    if k_e is not None and d > 1:
        if not forced and K % (group * d) == 0:
            k_tag = k_e
        else:
            spec_k = None
            if not quiet and not forced:
                obs.log(
                    "dist.sparse_k_replicated", level="warn",
                    leaf=path or axes_str, axis=str(k_e), dim=K,
                    devices=d, idx_bits=st.idx_bits,
                    warn=(f"compressed leaf {path or axes_str}: K={K} "
                          f"cannot shard over mesh axis {k_e!r} "
                          f"({d} devices, needs K % {group * d} == 0 for "
                          f"{'2-bit-packed' if group == 8 else 'int8'} "
                          f"index planes); vals AND idx replicate along K"))
    vals_spec = P(*lead, spec_k, n_keep)
    idx_spec = P(*lead, spec_k, n_keep)
    exec_entries = lead[1:] if names[0] == "layers" else lead
    tag = (_site_for(path),
           *(e if _axes_size(mesh, e) > 1 else None for e in exec_entries),
           k_tag,
           n_keep if _axes_size(mesh, n_keep) > 1 else None)
    if all(e is None for e in tag[1:]):
        tag = None
    return vals_spec, idx_spec, tag


def sparse_leaf_sharding(axes_str: str | None, st: SparseTensor,
                         rules: ShardingRules,
                         path: str = "") -> SparseTensor:
    """Sharding for one SparseTensor leaf, as a matching pytree node.

    Both components reuse the dense kernel's logical axis names; the K dim
    is decided leaf-wise by :func:`sparse_component_layout` (all-or-nothing
    across vals/idx, loud on fallback).  Returned as a SparseTensor of
    NamedShardings - carrying the *input* leaf's static aux (idx_bits and
    any shard tag) verbatim, so the tree is a valid device_put /
    in_shardings target whether or not the params were tagged first.
    """
    vals_spec, idx_spec, _ = sparse_component_layout(axes_str, st, rules,
                                                     path=path)
    return SparseTensor(NamedSharding(rules.mesh, vals_spec),
                        NamedSharding(rules.mesh, idx_spec),
                        idx_bits=st.idx_bits, shard=st.shard)


def tag_compressed(axes_tree: PyTree, params: PyTree,
                   rules: ShardingRules) -> PyTree:
    """Stamp every SparseTensor leaf with its tensor-parallel dispatch tag.

    The tag ((site, *mesh-axis entries), static aux - see
    ``SparseTensor.shard``) is what ``sparse.apply`` dispatches on at trace
    time: K-sharded leaves route through the shard-mapped kernels with
    explicit psum accumulation.  Quiet (no fallback warnings): callers pair
    this with :func:`params_sharding`, which is the loud pass.  Every other
    leaf passes through untouched (by identity).
    """
    def leaf(kp, axes_str, w):
        if isinstance(w, SparseTensor):
            _, _, tag = sparse_component_layout(
                axes_str, w, rules, path=keystr(kp), quiet=True)
            return w.with_shard(tag) if tag != w.shard else w
        return w

    return tree_map_with_path(leaf, axes_tree, params,
                              is_leaf=lambda x: x is None)


def params_sharding(axes_tree: PyTree, shapes_tree: PyTree,
                    rules: ShardingRules) -> PyTree:
    """'|'-joined logical-axis strings + shapes -> NamedSharding tree.

    ``shapes_tree`` may be ``models.model.param_shapes`` output or an actual
    params tree; SparseTensor leaves (compressed kernels) get component-wise
    specs via :func:`sparse_leaf_sharding`, BitMask leaves replicate.
    """
    def leaf(kp, axes_str, shape_like):
        if isinstance(shape_like, SparseTensor):
            return sparse_leaf_sharding(axes_str, shape_like, rules,
                                        path=keystr(kp))
        if isinstance(shape_like, BitMask):
            return BitMask(NamedSharding(rules.mesh, P()), shape_like.shape)
        if axes_str is None or shape_like is None:
            return NamedSharding(rules.mesh, P())
        names = axes_str.split("|")
        spec = spec_for_shape(rules, names, shape_like.shape)
        return NamedSharding(rules.mesh, spec)

    return tree_map_with_path(leaf, axes_tree, shapes_tree,
                              is_leaf=lambda x: x is None)


def init_params_sharded(cfg, key: jax.Array, rules: ShardingRules) -> PyTree:
    """``models.model.init_params`` built in place on the mesh: each device
    initializes only its own shards, so no device ever holds the whole
    fp32 tree (the values equal the unsharded init's)."""
    from repro.models import model as M
    sh = params_sharding(M.param_axes(cfg), M.param_shapes(cfg), rules)
    return jax.jit(M.init_params, static_argnums=0, out_shardings=sh)(
        cfg, key)

def search_state_sharding(axes_tree: PyTree, state, rules: ShardingRules):
    """NamedSharding tree for a ``core.mirror.SearchState`` on the mesh.

    The trainable copy W inherits the dense parameter rules verbatim (it IS
    the params tree in fp32); Gamma and V are prunable-leaf shadows of W, so
    each non-None leaf reuses its kernel's sharding - the three full-size
    fp32 trees of the mirror-descent search live distributed instead of
    replicated.  step/rng replicate.  The result pairs leaf-for-leaf with
    the state for ``jax.device_put`` / jit in_shardings.
    """
    from repro.core.mirror import SearchState
    base = params_sharding(axes_tree, state.W, rules)
    rep = NamedSharding(rules.mesh, P())

    def gv(g, sh):
        return None if g is None else sh

    return SearchState(
        W=base,
        Gamma=jax.tree.map(gv, state.Gamma, base,
                           is_leaf=lambda x: x is None),
        V=jax.tree.map(gv, state.V, base, is_leaf=lambda x: x is None),
        step=rep, rng=rep)


def stacked_batch_sharding(stacked_tree: PyTree, mesh) -> PyTree:
    """Scan-stacked calibration chunks, leaves (steps, B, ...): the scan
    axis stays unsharded (consumed sequentially), the batch dim shards over
    the data axes when divisible - the chunked search streams each step's
    microbatch already distributed."""
    data = _one(_data_axes(mesh))
    dp = 1
    for a in _data_axes(mesh):
        dp *= mesh.shape[a]

    def leaf(s):
        if s is None:
            return NamedSharding(mesh, P())
        spec: list = [None] * len(s.shape)
        if len(s.shape) >= 2 and s.shape[1] % dp == 0:
            spec[1] = data
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(leaf, stacked_tree, is_leaf=lambda x: x is None)


def batch_sharding_tree(batch_tree: PyTree, mesh) -> PyTree:
    """Input batches: leading batch dim over the data axes, rest replicated."""
    data = _one(_data_axes(mesh))
    dp = 1
    for a in _data_axes(mesh):
        dp *= mesh.shape[a]

    def leaf(s):
        if s is None:
            return NamedSharding(mesh, P())
        b = _one(tuple(a for a in _data_axes(mesh)))
        spec = [b if s.shape and s.shape[0] % dp == 0 else None]
        spec += [None] * (len(s.shape) - 1)
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(leaf, batch_tree, is_leaf=lambda x: x is None)


def cache_sharding(cache_tree: PyTree, mesh) -> PyTree:
    """Decode KV caches, leaves (layers, B, capacity, ...).

    * layers axis: never sharded (scanned over),
    * B > 1: batch over the data axes, capacity over "model" (decode
      attention reduces over capacity with a partial softmax - GSPMD lowers
      it to a tiny all-reduce, no KV all-gather),
    * B == 1 (long-context): capacity over every divisible mesh axis.
    """
    data = _data_axes(mesh)
    dp = 1
    for a in data:
        dp *= mesh.shape[a]

    def leaf(s):
        if s is None:
            return NamedSharding(mesh, P())
        shape = s.shape
        spec: list = [None] * len(shape)
        if len(shape) >= 3:
            B, C = shape[1], shape[2]
            if B > 1 and B % dp == 0:
                spec[1] = _one(data)
                if C % mesh.shape["model"] == 0:
                    spec[2] = "model"
            else:
                axes = tuple(a for a in data + ("model",)
                             if C % mesh.shape[a] == 0)
                # nested-tuple product divisibility
                n = 1
                keep = []
                for a in axes:
                    if C % (n * mesh.shape[a]) == 0:
                        keep.append(a)
                        n *= mesh.shape[a]
                if keep:
                    spec[2] = keep[0] if len(keep) == 1 else tuple(keep)
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(leaf, cache_tree, is_leaf=lambda x: x is None)
