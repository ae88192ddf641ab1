"""Registered jit surfaces for the static auditor and contract checks.

A *surface* is one jitted hot path plus concrete smoke arguments to trace
it with: the ServeEngine step functions (decode, bucketed prefill, the
slot write) and the calibration search chunk.  The registry builds each
exactly the way production does - sparse bf16 params through
``sparse.apply.sparsify_params``, K-shard tags + mesh rules through
``ServeEngine``, the search chunk through ``core.calibrate.make_chunk_fn``
with ``donate_argnums=0`` - so the audited jaxpr IS the served jaxpr, not
a lookalike.

Smoke configs keep tracing cheap (seconds on CPU); the *static* facts the
contracts gate on (collectives per site per layer, zero host callbacks, no
silent f32 upcasts, donation declared) are scale-free.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

__all__ = ["Surface", "serve_surfaces", "search_surface", "all_surfaces"]


@dataclasses.dataclass
class Surface:
    """One auditable jit entry point with trace-ready arguments.

    policy: "serve" surfaces must have ZERO large bf16->f32 upcasts;
    "train" surfaces legitimately upcast in the backward pass (weight
    gradients convert to f32 at the transpose of the intentional
    ``k.astype(COMPUTE_DTYPE)`` forward downcasts), so their upcast count
    is pinned by the golden instead of forced to zero.
    """
    name: str
    fn: Callable
    args: tuple
    donate_argnums: tuple = ()
    policy: str = "serve"


def _sparse_smoke(arch: str, *, idx_bits: int = 2):
    """Smoke config + 2:4-sparse bf16 compressed params (mirrors the
    serving tests' setup byte for byte)."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_smoke_config
    from repro.core import masks as masks_mod
    from repro.core import metrics as metrics_mod
    from repro.core.prunable import prunable_map
    from repro.models import model as M
    from repro.sparse import apply as apply_mod
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.key(0))
    pr = prunable_map(params)
    scores = metrics_mod.metric_tree(
        "magnitude", params, jax.tree.map(lambda _: None, pr), pr)
    masks = masks_mod.nm_masks(scores)
    sparse = apply_mod.sparsify_params(
        params, masks, axes=M.param_axes(cfg), idx_bits=idx_bits,
        dtype=jnp.bfloat16)
    return cfg, sparse


def serve_surfaces(arch: str = "llama3.2-1b", *,
                   mesh_shape: tuple | None = (2, 2), sparse: bool = True,
                   slots: int = 2, capacity: int = 32,
                   prefill_bucket: int = 8, spec_k: int = 4
                   ) -> list[Surface]:
    """decode / prefill_<bucket> / write_slot / verify_<k> for one smoke
    engine.

    ``verify_<k>`` is the speculative-decode verifier (teacher-forced
    batched pass over k fed tokens, ``serve.spec``); it registers only for
    archs whose layer kinds support spec mode (full-ring attention,
    ``serve.spec.SPEC_SAFE_KINDS``, no sliding window) - the same gate the
    decoder enforces, so the audited surface set matches what serving can
    actually dispatch.

    mesh_shape (data, model) requires that many devices (force host
    devices via ``python -m repro.analysis --devices N ...`` or the
    XLA_FLAGS env); None audits the single-device engine.
    """
    import jax
    from repro.launch.mesh import make_mesh
    import jax.numpy as jnp
    from repro.dist.axes import make_rules
    from repro.models import model as M
    from repro.serve.engine import ServeEngine
    from repro.serve.spec import SPEC_SAFE_KINDS
    if sparse:
        cfg, params = _sparse_smoke(arch)
    else:
        from repro.configs.base import get_smoke_config
        cfg = get_smoke_config(arch)
        params = M.init_params(cfg, jax.random.key(0))
    rules = None
    if mesh_shape is not None:
        mesh = make_mesh(tuple(mesh_shape), ("data", "model"))
        rules = make_rules(mesh)
    eng = ServeEngine(cfg, params, slots=slots, capacity=capacity,
                      rules=rules)
    toks = jnp.zeros((slots,), jnp.int32)
    pos = jnp.zeros((slots,), jnp.int32)
    ptoks = jnp.zeros((1, prefill_bucket), jnp.int32)
    # NOTE: the decode surface stays at index 0 (zoo dry-runs and the
    # memory planner key off it); new surfaces append at the end
    out = [
        Surface("decode", eng._decode, (eng.params, toks, eng.caches, pos)),
        Surface(f"prefill_{prefill_bucket}", eng.fns.prefill(prefill_bucket),
                (eng.params, ptoks)),
        Surface("write_slot", eng.fns.write_slot,
                (eng.caches, eng.fns.blank_row(), jnp.int32(0))),
    ]
    if set(cfg.layer_kinds) <= SPEC_SAFE_KINDS and not cfg.sliding_window:
        vtoks = jnp.zeros((slots, spec_k), jnp.int32)
        out.append(Surface(f"verify_{spec_k}", eng.fns.verify(spec_k),
                           (eng.params, vtoks, eng.caches, pos)))
    return out


def search_surface(arch: str = "llama3.2-1b", *, chunk: int = 2,
                   batch: int = 2, seq: int = 32,
                   metric: str = "wanda") -> Surface:
    """The calibration search chunk run_search jits (donated state)."""
    import jax
    from functools import partial
    from repro.configs.base import PruneConfig, get_smoke_config
    from repro.core import calibrate, mirror
    from repro.core.prunable import prunable_map
    from repro.data.synthetic import batches_for
    from repro.models import model as M
    from repro.optim.losses import lm_loss
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.key(0))
    batches = batches_for(cfg, n=chunk, batch=batch, seq=seq, split="calib")
    pcfg = PruneConfig(local_metric=metric, steps=chunk, scan_chunk=chunk)
    stats = calibrate.collect_stats(cfg, params, batches, pcfg=pcfg)
    prunable = prunable_map(params)
    state = mirror.init_search(params, jax.random.key(17))
    stacked = calibrate._stack_chunk(batches, 0, chunk)
    fn = jax.jit(calibrate.make_chunk_fn(pcfg, partial(lm_loss, cfg), stats,
                                         prunable),
                 donate_argnums=0)
    return Surface("search_chunk", fn, (state, stacked),
                   donate_argnums=(0,), policy="train")


def all_surfaces(arch: str = "llama3.2-1b", *,
                 mesh_shape: tuple | None = (2, 2),
                 include_search: bool | None = None) -> list[Surface]:
    """The full registry for one arch.  The search surface runs on the
    default (replicated) placement, so it is only included when auditing
    without a mesh unless explicitly requested."""
    out = serve_surfaces(arch, mesh_shape=mesh_shape)
    if include_search is None:
        include_search = mesh_shape is None
    if include_search:
        out.append(search_surface(arch))
    return out
