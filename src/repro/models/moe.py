"""Mixture-of-Experts FFN with scatter-based token dispatch.

Dispatch is gather/scatter (argfree cumsum positioning), NOT one-hot einsum,
so compiled HLO FLOPs reflect the true active-expert compute (important for
the roofline's MODEL_FLOPS / HLO_FLOPS ratio).

Expert banks execute through ``common.expert_dense``: 2:4-compressed
SparseTensor banks (``sparse.apply.sparsify_params``) run the expert-grid
``nm_matmul_expert`` kernel over the dispatch buffer, dense banks keep the
einsum.  During calibration the stats tape records the dispatch buffer with
per-expert routed-token counts so capacity padding never dilutes saliency.

Sharding: if num_experts divides the `model` axis the expert dim is
expert-parallel ("experts" logical axis); otherwise each expert's hidden dim
is tensor-parallel ("mlp").
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.dist.axes import constrain
from repro.models import common as cm
from repro.models.common import Builder


PyTree = Any


def moe_init(b: Builder, *, d_model: int, d_ff: int, num_experts: int,
             num_shared: int = 0, expert_sharded: bool = False) -> PyTree:
    e_ax = "experts" if expert_sharded else None
    f_ax = None if expert_sharded else "mlp"
    p = {
        "router": {"kernel": b.param((d_model, num_experts), ("embed", None),
                                     scale=d_model ** -0.5)},
        "up": {"kernel": b.param((num_experts, d_model, d_ff),
                                 (e_ax, "embed", f_ax))},
        "gate": {"kernel": b.param((num_experts, d_model, d_ff),
                                   (e_ax, "embed", f_ax))},
        "down": {"kernel": b.param((num_experts, d_ff, d_model),
                                   (e_ax, f_ax, "embed"))},
    }
    if num_shared:
        from repro.models.mlp import mlp_init
        p["shared"] = mlp_init(b, d_model, num_shared * d_ff, gated=True)
    return p


def _dp_setup():
    """(n_groups, batch_axes, mesh) from the installed sharding rules."""
    from repro.dist.axes import current_rules
    rules = current_rules()
    if rules is None:
        return 1, (), None
    n = 1
    batch_axes = rules.rules.get("batch") or ()
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    batch_axes = tuple(a for a in batch_axes if a in rules.mesh.axis_names)
    for a in batch_axes:
        n *= rules.mesh.shape[a]
    return n, batch_axes, rules.mesh


def _positions_in_expert(flat_e: jax.Array, E: int, C: int):
    """flat_e: (..., A) expert ids -> (e_idx, p_idx, keep, onehot)."""
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos_all = jnp.cumsum(oh, axis=-2) - oh
    pos = jnp.take_along_axis(pos_all, flat_e[..., None], axis=-1)[..., 0]
    keep = pos < C
    e_idx = jnp.where(keep, flat_e, E)  # OOB -> dropped by scatter
    p_idx = jnp.where(keep, pos, 0)
    return e_idx, p_idx, keep, oh


def moe_apply(p: PyTree, x: jax.Array, *, top_k: int,
              capacity_factor: float = 1.25, act: str = "silu",
              expert_sharded: bool = False) -> tuple[jax.Array, jax.Array]:
    """Returns (y, aux_load_balance_loss).

    Dispatch is GROUP-LOCAL: tokens are viewed as (dp_groups, T/dp, d)
    aligned with the batch sharding, capacity positions come from a cumsum
    *within* each group, and the scatter/gather carry the group dim - so
    GSPMD keeps every dispatch buffer dp-sharded instead of replicating a
    global-capacity buffer (a ~16 GB/device temp for mixtral otherwise).
    """
    orig_shape = x.shape
    d = x.shape[-1]
    T = x.size // d
    G, batch_axes, mesh = _dp_setup()
    if T % G != 0 or (T // G) < 8:
        G, batch_axes, mesh = 1, (), None
    Tl = T // G
    xg = constrain(x.reshape(G, Tl, d), "batch", None, None)
    E = p["router"]["kernel"].shape[-1]
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        p["router"]["kernel"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)  # (G, Tl, E)
    gate_vals, idx = jax.lax.top_k(probs, top_k)  # (G, Tl, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    C = int(capacity_factor * Tl * top_k / E)
    C = min(max(8, -(-C // 8) * 8), Tl)
    flat_e = idx.reshape(G, Tl * top_k)  # expert id per assignment

    def dispatch_local(xg_l, flat_e_l):
        """Per-dp-shard scatter into (g_loc, E, C, d); runs under shard_map
        so the scatter is device-local (GSPMD replicates it otherwise)."""
        gl = xg_l.shape[0]
        e_idx, p_idx, keep, _ = _positions_in_expert(flat_e_l, E, C)
        src = jnp.repeat(xg_l, top_k, axis=1)  # (gl, Tl*k, d)
        g_iota = jnp.broadcast_to(jnp.arange(gl)[:, None], e_idx.shape)
        buf = jnp.zeros((gl, E, C, d), xg_l.dtype)
        buf = buf.at[g_iota, e_idx, p_idx].set(src, mode="drop")
        return buf, e_idx, p_idx, keep

    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        dispatch_local = jax.shard_map(
            dispatch_local, mesh=mesh,
            in_specs=(P(batch_axes, None, None), P(batch_axes, None)),
            out_specs=(P(batch_axes, None, None, None), P(batch_axes, None),
                       P(batch_axes, None), P(batch_axes, None)))
    buf, e_idx, p_idx, keep = dispatch_local(xg, flat_e)
    buf = constrain(buf, "batch", None, None, None)

    from repro.core import tape as _tape
    t = _tape.current_tape()
    if t is not None:  # per-(expert, input-feature) activation stats
        # The capacity buffer is zero-padded (unfilled slots, dropped
        # tokens): zeros add nothing to the sum of squares, but the
        # per-expert sample size is the routed-row count, not G*C - record
        # it so the stat renormalizes to the T tokens a dense-FFN layer
        # sees instead of reading diluted under one global budget.
        routed = jnp.sum(e_idx[..., None] == jnp.arange(E), axis=(0, 1))
        t.record(p["up"]["kernel"], buf.swapaxes(0, 1),   # (E, G, C, d)
                 count=routed, ref_count=T)
        t.record(p["gate"]["kernel"], buf.swapaxes(0, 1),
                 count=routed, ref_count=T)
    f_ax = None if expert_sharded else "mlp"
    e_ax = "experts" if expert_sharded else None
    # expert_dense dispatches on the bank leaf type: compressed SparseTensor
    # banks run the expert-grid nm_matmul_expert kernel over the dispatch
    # buffer, dense banks keep the einsum.  The pair helper fuses the shared
    # reduction dim when both banks are K-shard-tagged: one deferred psum
    # for the whole up+gate projection group.
    h, g = cm.expert_dense_pair(p["up"], p["gate"], buf)
    if act == "silu":
        g = jax.nn.silu(g)
    else:
        g = jax.nn.gelu(g, approximate=True)
    h = h * g
    h = constrain(h, "batch", e_ax, None, f_ax)
    if t is not None:
        t.record(p["down"]["kernel"], h.swapaxes(0, 1),
                 count=routed, ref_count=T)
    out_buf = cm.expert_dense(p["down"], h)
    out_buf = constrain(out_buf, "batch", None, None, None)

    def combine_local(out_buf_l, e_idx_l, p_idx_l, keep_l, gate_l):
        gl = out_buf_l.shape[0]
        g_iota = jnp.broadcast_to(jnp.arange(gl)[:, None], e_idx_l.shape)
        y_tk = out_buf_l.at[g_iota, e_idx_l, p_idx_l].get(
            mode="fill", fill_value=0)  # (gl, Tl*k, d)
        y_tk = y_tk * keep_l[..., None].astype(y_tk.dtype)
        y_tk = y_tk * gate_l.reshape(gl, -1)[..., None].astype(y_tk.dtype)
        return jnp.sum(y_tk.reshape(gl, Tl, top_k, d), axis=2)

    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        combine_local = jax.shard_map(
            combine_local, mesh=mesh,
            in_specs=(P(batch_axes, None, None, None), P(batch_axes, None),
                      P(batch_axes, None), P(batch_axes, None),
                      P(batch_axes, None, None)),
            out_specs=P(batch_axes, None, None))
    y = combine_local(out_buf, e_idx, p_idx, keep, gate_vals)
    y = y.reshape(orig_shape)

    if "shared" in p:
        from repro.models.mlp import mlp_apply
        y = y + mlp_apply(p["shared"], x, act=act)

    # Switch-style load-balance aux loss: E * sum_e f_e * P_e, f_e = the
    # fraction of assignments routed to e (sums to 1 across experts), so
    # uniform routing gives aux == 1 and imbalance grows it.
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.float32)
    frac = jnp.mean(oh, axis=(0, 1)) * E
    mean_prob = jnp.mean(probs, axis=(0, 1)) * E
    aux = jnp.mean(frac * mean_prob)
    return y, aux
