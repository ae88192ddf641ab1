"""Tensor-parallel sparse execution: shard_map wrappers with explicit
K-partial accumulation.

GSPMD never K-shards the compressed kernels: ``vals`` (K/2, N) and ``idx``
(K/2 | K/8, N) are two pytree leaves whose reduction dims the partitioner
cannot connect through a Pallas call, so PR 2's component-wise sharding
specs executed replicated-or-N-sharded.  These wrappers make the contraction
explicit: each device runs the Pallas kernel on its local (K_loc/2, N_loc)
vals and (K_loc/8, N_loc) packed-idx shards producing a *float32 partial*,
and a single ``jax.lax.psum`` over the K mesh axes combines partials before
the one cast back to the activation dtype.

The psum is *deferred across projection groups*: the fused gate/up pair and
the MoE up/gate expert banks each run two local kernels and then ONE
variadic ``psum((h, g), axes)`` - one collective per projection group, not
per kernel.  Sites are labeled (mlp / attn / moe / attn_kv) and every
wrapper increments ``dist.psum`` / ``dist.psum_bytes`` at trace time (once
per compiled trace - the static per-decode-step collective count the bench
asserts on) and records ``dist.collective_ms`` on eager calls.

``decode_attend_sharded`` is the KV-cache sibling: capacity-sharded caches
run a local flash partial (TPU) or an exact-mimic masked softmax (CPU
parity), then pmax/psum combine - a sharded fleet member never falls back
to replicated weights or a replicated cache.

Leaves whose K cannot shard but whose N (or expert) dim does carry a tag
with a None K entry: they run under the same shard_map with no psum, since
on a TPU a Pallas kernel cannot be partitioned automatically.

``REPRO_FORCE_REPLICATED=1`` disables every K-sharded path (no K entry is
stamped, caches stay per-GSPMD) - the escape hatch when a mesh/collective
bug needs bisecting.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.dist.axes import current_rules

FORCE_REPLICATED_ENV = "REPRO_FORCE_REPLICATED"


def replicated_forced() -> bool:
    """Env escape hatch: force the replicated/GSPMD fallback everywhere."""
    return os.environ.get(FORCE_REPLICATED_ENV, "") not in ("", "0")


def _ax_tuple(entry) -> tuple[str, ...]:
    """Spec entry (None | name | tuple of names) -> tuple of mesh axes."""
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def axes_size(mesh, entry) -> int:
    n = 1
    for a in _ax_tuple(entry):
        n *= mesh.shape[a]
    return n


def k_sharded(st) -> bool:
    """Does this leaf's tag route through the shard-mapped kernels here?

    True when the leaf carries a non-None K entry AND rules are installed
    (the tag is stamped from the same rules the engine traces under, so the
    mesh axes are guaranteed present).
    """
    if replicated_forced():
        return False
    if getattr(st, "shard", None) is None or st.k_shard is None:
        return False
    return current_rules() is not None


def tp_routed(st) -> bool:
    """Does this leaf run under a shard_map here?  True when it carries a
    tensor-parallel tag (K-, N- or expert-sharded) and rules are installed;
    only a K-sharded tag adds the psum."""
    return (getattr(st, "shard", None) is not None
            and current_rules() is not None)


def _k_axes(st) -> tuple[str, ...]:
    """Mesh axes the K-partial psum reduces, () for a K-replicated leaf."""
    return _ax_tuple(st.shard[-2]) if k_sharded(st) else ()


def pair_k_sharded(st_a, st_b) -> bool:
    """Can a gate/up pair share one deferred psum? (same K mesh axes)"""
    return (k_sharded(st_a) and k_sharded(st_b)
            and st_a.shard[-2] == st_b.shard[-2]
            and st_a.vals.shape[-2] == st_b.vals.shape[-2])


def _eager(*arrays) -> bool:
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def _count(site: str, payload_bytes: int, n_psum: int = 1) -> None:
    """Collective accounting.  Under jit this runs at trace time, so the
    counters advance once per compiled trace: the value IS the static
    per-step collective count (and per-device payload bytes)."""
    obs.inc("dist.psum", n_psum, site=site)
    obs.inc("dist.psum_bytes", payload_bytes, site=site)


def _timed(site: str, eager: bool, fn, *args):
    if eager and obs.enabled():
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        obs.observe("dist.collective_ms", (time.perf_counter() - t0) * 1e3,
                    site=site)
        return out
    return fn(*args)


def _local_nm(x, vals, idx, expert: bool = False):
    """One device's kernel call on shard-local operands -> f32 partial.

    Layout is inferred from the *local* shapes (the vals/idx row ratio is
    sharding-invariant, see ``nm_spmm.infer_layout``); block selection sees
    local dims too, so a K_loc smaller than the global tile caps cleanly.
    """
    from repro.kernels.nm_spmm import (infer_layout, nm_matmul,
                                       nm_matmul_expert)
    from repro.sparse.apply import _run_nm
    layout = infer_layout(2 * vals.shape[-2], idx.shape)
    return _run_nm(x, vals, idx, layout,
                   kernel=nm_matmul_expert if expert else nm_matmul,
                   out_dtype=jnp.float32)


# ---------------------------------------------------------------------------
# 2-D kernels (MLP / attention projections)
# ---------------------------------------------------------------------------

def nm_dense_sharded(st, x2: jax.Array, *, site: str) -> jax.Array:
    """x2 (M, K) @ tagged compressed (K, N) -> (M, N); one psum when K is
    sharded, none when only N is."""
    rules = current_rules()
    mesh = rules.mesh
    k_axes = _k_axes(st)
    k_e = k_axes or None
    n_e = st.shard[-1]
    out_dt = x2.dtype
    M = x2.shape[0]
    n_loc = st.shape[-1] // axes_size(mesh, n_e)
    if k_axes:
        _count(site, M * n_loc * 4)
    idx_plane = st.idx if st.kernel_layout == "packed2" else st.unpacked_idx()

    def local(xl, vl, il):
        # the site: scope lands in the psum eqn's name_stack, so the jaxpr
        # auditor attributes collectives per site without running anything
        with jax.named_scope(f"site:{site}"):
            y = _local_nm(xl, vl, il)
            if k_axes:
                y = jax.lax.psum(y, k_axes)
            return y.astype(out_dt)

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(None, k_e), P(k_e, n_e), P(k_e, n_e)),
                      out_specs=P(None, n_e), check_vma=False)
    return _timed(site, _eager(x2), f, x2, st.vals.astype(out_dt), idx_plane)


def nm_dense2_sharded(st_a, st_b, x2: jax.Array, *, site: str
                      ) -> tuple[jax.Array, jax.Array]:
    """Fused pair sharing K (gated-MLP up+gate): two local kernels, ONE
    deferred variadic psum over the pair -> one collective for the group."""
    rules = current_rules()
    mesh = rules.mesh
    k_e = st_a.shard[-2]
    n_a, n_b = st_a.shard[-1], st_b.shard[-1]
    k_axes = _ax_tuple(k_e)
    out_dt = x2.dtype
    M = x2.shape[0]
    payload = (M * (st_a.shape[-1] // axes_size(mesh, n_a))
               + M * (st_b.shape[-1] // axes_size(mesh, n_b))) * 4
    _count(site, payload)
    ia = st_a.idx if st_a.kernel_layout == "packed2" else st_a.unpacked_idx()
    ib = st_b.idx if st_b.kernel_layout == "packed2" else st_b.unpacked_idx()

    def local(xl, va, ila, vb, ilb):
        with jax.named_scope(f"site:{site}"):
            ya = _local_nm(xl, va, ila)
            yb = _local_nm(xl, vb, ilb)
            ya, yb = jax.lax.psum((ya, yb), k_axes)
            return ya.astype(out_dt), yb.astype(out_dt)

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(None, k_e), P(k_e, n_a), P(k_e, n_a),
                                P(k_e, n_b), P(k_e, n_b)),
                      out_specs=(P(None, n_a), P(None, n_b)), check_vma=False)
    return _timed(site, _eager(x2), f, x2, st_a.vals.astype(out_dt), ia,
                  st_b.vals.astype(out_dt), ib)


# ---------------------------------------------------------------------------
# Expert banks (MoE)
# ---------------------------------------------------------------------------

def nm_moe_sharded(st, x3: jax.Array, *, site: str = "moe") -> jax.Array:
    """x3 (E, M, K) @ tagged expert bank (E, K, N) -> (E, M, N).

    The expert grid rides inside ONE shard_map: every expert's partial comes
    out of a single ``nm_matmul_expert`` call and one psum combines the
    whole bank - not one collective per expert (none when K replicates).
    """
    rules = current_rules()
    mesh = rules.mesh
    e_e, n_e = st.shard[-3], st.shard[-1]
    k_axes = _k_axes(st)
    k_e = k_axes or None
    out_dt = x3.dtype
    E, M, _ = x3.shape
    e_loc = E // axes_size(mesh, e_e)
    n_loc = st.shape[-1] // axes_size(mesh, n_e)
    if k_axes:
        _count(site, e_loc * M * n_loc * 4)
    idx_plane = st.idx if st.kernel_layout == "packed2" else st.unpacked_idx()

    def local(xl, vl, il):
        with jax.named_scope(f"site:{site}"):
            y = _local_nm(xl, vl, il, expert=True)
            if k_axes:
                y = jax.lax.psum(y, k_axes)
            return y.astype(out_dt)

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(e_e, None, k_e), P(e_e, k_e, n_e),
                                P(e_e, k_e, n_e)),
                      out_specs=P(e_e, None, n_e), check_vma=False)
    return _timed(site, _eager(x3), f, x3, st.vals.astype(out_dt), idx_plane)


def nm_moe2_sharded(st_up, st_gate, x3: jax.Array, *, site: str = "moe"
                    ) -> tuple[jax.Array, jax.Array]:
    """Fused up+gate expert banks: two local expert-grid kernels, one
    deferred variadic psum across the pair AND the expert grid."""
    rules = current_rules()
    mesh = rules.mesh
    e_e, k_e = st_up.shard[-3], st_up.shard[-2]
    n_u, n_g = st_up.shard[-1], st_gate.shard[-1]
    k_axes = _ax_tuple(k_e)
    out_dt = x3.dtype
    E, M, _ = x3.shape
    e_loc = E // axes_size(mesh, e_e)
    payload = (e_loc * M * (st_up.shape[-1] // axes_size(mesh, n_u))
               + e_loc * M * (st_gate.shape[-1] // axes_size(mesh, n_g))) * 4
    _count(site, payload)
    iu = (st_up.idx if st_up.kernel_layout == "packed2"
          else st_up.unpacked_idx())
    ig = (st_gate.idx if st_gate.kernel_layout == "packed2"
          else st_gate.unpacked_idx())

    def local(xl, vu, ilu, vg, ilg):
        with jax.named_scope(f"site:{site}"):
            h = _local_nm(xl, vu, ilu, expert=True)
            g = _local_nm(xl, vg, ilg, expert=True)
            h, g = jax.lax.psum((h, g), k_axes)
            return h.astype(out_dt), g.astype(out_dt)

    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(e_e, None, k_e), P(e_e, k_e, n_u),
                                P(e_e, k_e, n_u), P(e_e, k_e, n_g),
                                P(e_e, k_e, n_g)),
                      out_specs=(P(e_e, None, n_u), P(e_e, None, n_g)),
                      check_vma=False)
    return _timed(site, _eager(x3), f, x3, st_up.vals.astype(out_dt), iu,
                  st_gate.vals.astype(out_dt), ig)


# ---------------------------------------------------------------------------
# Decode attention over a capacity-sharded KV cache
# ---------------------------------------------------------------------------

def kv_shard_axes(B: int, C: int) -> tuple[str, ...]:
    """Mesh axes of the decode-KV capacity dim, () when the sharded path is
    off.  Mirrors ``dist.sharding.cache_sharding``'s B > 1 layout (capacity
    over "model") so the shard_map in_specs match how the engine placed the
    caches - no resharding on entry.
    """
    rules = current_rules()
    if rules is None or replicated_forced():
        return ()
    mesh = rules.mesh
    if "model" not in mesh.axis_names:
        return ()
    m = mesh.shape["model"]
    if m <= 1 or B <= 1 or C % m:
        return ()
    return ("model",)


def decode_attend_sharded(qg: jax.Array, cache_k: jax.Array,
                          cache_v: jax.Array, ok: jax.Array, *,
                          axes: tuple[str, ...], scale: float) -> jax.Array:
    """Partial-softmax decode attention over capacity-sharded KV.

    qg (B, K, G, D) replicated; cache_k/v (B, C, K, D) capacity-sharded over
    ``axes``; ok (B, C) valid-slot mask (position + window, precomputed by
    the caller so both paths mask identically).

    CPU path mimics the replicated einsum element-for-element:
    local scores, global max via pmax, exp/sum, the same
    ``(p / l).astype(v.dtype)`` cast the oracle makes *before* the PV
    einsum, then a psum of the f32 PV partials - token parity with the
    replicated engine.  TPU path runs the flash partial kernel per shard
    and combines (l, acc) with ONE variadic psum after an m-pmax.
    """
    from repro.kernels import ops
    from repro.kernels.flash_decode import flash_decode_partial
    rules = current_rules()
    mesh = rules.mesh
    B, Kh, G, _ = qg.shape
    Dv = cache_v.shape[-1]
    NEG = -1e30  # attention.NEG_INF: both paths mask with the same constant

    if ops.backend() == "cpu":
        # exact-mimic combine: 1 pmax + 2 psums
        _count("attn_kv", B * Kh * G * (1 + Dv) * 4, n_psum=2)

        def local(q, ck, cv, okl):
            with jax.named_scope("site:attn_kv"):
                s = jnp.einsum("bkgd,bckd->bkgc", q, ck,
                               preferred_element_type=jnp.float32) * scale
                s = jnp.where(okl[:, None, None, :], s, NEG)
                m = jax.lax.pmax(jnp.max(s, axis=-1, keepdims=True), axes)
                p = jnp.exp(s - m)
                l = jax.lax.psum(jnp.sum(p, axis=-1, keepdims=True), axes)
                w = (p / l).astype(cv.dtype)
                o = jnp.einsum("bkgc,bckd->bkgd", w, cv,
                               preferred_element_type=jnp.float32)
                return jax.lax.psum(o, axes).astype(qg.dtype)
    else:
        # flash partial + 1 pmax + 1 variadic psum over (l, acc)
        _count("attn_kv", B * Kh * G * (1 + Dv) * 4, n_psum=1)

        def local(q, ck, cv, okl):
            with jax.named_scope("site:attn_kv"):
                bias = jnp.where(okl, 0.0, NEG).astype(jnp.float32)
                acc, m, l = flash_decode_partial(q, ck, cv, bias,
                                                 scale=scale)
                mg = jax.lax.pmax(m, axes)
                corr = jnp.exp(m - mg)
                l, acc = jax.lax.psum((l * corr, acc * corr), axes)
                return (acc / jnp.maximum(l, 1e-30)).astype(qg.dtype)

    ax = axes[0] if len(axes) == 1 else axes
    f = jax.shard_map(local, mesh=mesh,
                      in_specs=(P(None, None, None, None),
                                P(None, ax, None, None),
                                P(None, ax, None, None), P(None, ax)),
                      out_specs=P(None, None, None, None), check_vma=False)
    return _timed("attn_kv", _eager(qg), f, qg, cache_k, cache_v, ok)
