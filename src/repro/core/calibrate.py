"""End-to-end UniPruning calibration drivers.

collect_stats   - activation stats over the calibration set (Algorithm 1,
                  line 1).  impl="jit" (default): the mesh-shardable
                  ``models.model.stats_sumsq`` pass, one compiled dispatch
                  per batch with per-layer stats stacked by ``lax.scan``.
                  impl="tape": the eager, unrolled StatsTape pass - the
                  parity oracle, asserted against the jitted pass in tests.
run_search      - N mirror-descent steps (lines 3-12), executed as
                  ``lax.scan``-chunked jitted dispatches with donated state
                  buffers; pass ``rules`` to run the whole search with
                  W/Gamma/V sharded on the mesh via ``dist.sharding``.
unipruning_prune- full pipeline: stats -> search -> Gamma -> masks(W0) at any
                  requested sparsity levels (one search, many budgets).
baseline_masks  - one-shot local-metric baselines (Magnitude/Wanda/RIA/
                  stochRIA) sharing the same stats and mask machinery.

Process-level entry point: ``repro.launch.calibrate`` runs stats -> search
once and persists the result as a ``sparse.bank.MaskBank`` artifact that
serving and the benchmarks consume without ever re-running this module.
"""
from __future__ import annotations

import functools
from functools import partial
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis import recompile
from repro.configs.base import ModelConfig, PruneConfig
from repro.core import masks as masks_mod
from repro.core import metrics as metrics_mod
from repro.core import mirror
from repro.core import tape as tape_mod
from repro.core.prunable import prunable_map
from repro.optim.losses import lm_loss

PyTree = Any

_is_none = lambda x: x is None


@functools.lru_cache(maxsize=None)
def _jit_stats_fn(cfg: ModelConfig):
    from repro.models import model as M
    return jax.jit(lambda p, b: M.stats_sumsq(cfg, p, b))


def collect_stats(cfg: ModelConfig, params: PyTree, batches: Iterable[dict],
                  *, impl: str = "jit", pcfg: PruneConfig | None = None,
                  rules=None) -> PyTree:
    """Per-input-feature ||X_j||_2 over the calibration set.

    pcfg: when given, only the first ``pcfg.stats_batches`` batches feed the
    pass (the one place that policy lives).  rules: installed sharding rules
    for the jitted pass - batches are device_put over the data axes and the
    model's own constraints shard the activations.
    """
    batches = list(batches)
    if pcfg is not None:
        batches = batches[:pcfg.stats_batches]
    assert batches, "collect_stats needs at least one calibration batch"

    if impl == "tape":
        t = tape_mod.StatsTape()
        with tape_mod.recording(t):
            for b in batches:
                lm_loss(cfg, params, b, unroll=True)
        return tape_mod.resolve_stats(t, params)
    if impl != "jit":
        raise ValueError(f"unknown stats impl {impl!r}; options: jit, tape")

    from repro.dist import axes as axes_mod
    from repro.dist import sharding as sharding_mod
    from repro.models import model as M
    fwd = _jit_stats_fn(cfg) if rules is None else \
        jax.jit(lambda p, b: M.stats_sumsq(cfg, p, b))
    ctx = axes_mod.use_rules(rules) if rules is not None else None
    acc = None
    try:
        if ctx is not None:
            ctx.__enter__()
        for b in batches:
            b = {k: jnp.asarray(v) for k, v in b.items()}
            if rules is not None:
                b = jax.device_put(b, sharding_mod.batch_sharding_tree(
                    b, rules.mesh))
            ss = fwd(params, b)
            acc = ss if acc is None else jax.tree.map(
                lambda a, s: None if a is None else a + s, acc, ss,
                is_leaf=_is_none)
    finally:
        if ctx is not None:
            ctx.__exit__(None, None, None)
    return jax.tree.map(lambda a: None if a is None else jnp.sqrt(a),
                        acc, is_leaf=_is_none)


def stats_parity(tape_stats: PyTree, jit_stats: PyTree, prunable: PyTree,
                 *, tol: float = 5e-2) -> tuple[float, bool, int]:
    """(worst per-prunable-leaf relative Frobenius error, pass flag, leaves).

    The shared parity criterion between the jitted pass and the tape
    oracle, used by both the test suite and the calibrate bench gate.
    Aggregate (not elementwise) on purpose: eager-vs-compiled execution can
    flip MoE top-k routing for near-tied experts, moving single rows
    between expert stats; the norm bounds that noise while catching real
    bugs (e.g. a dropped per-expert rescale shifts whole rows ~2x).
    """
    worst = 0.0
    checked = 0
    for t, j, p in zip(jax.tree.leaves(tape_stats, is_leaf=_is_none),
                       jax.tree.leaves(jit_stats, is_leaf=_is_none),
                       jax.tree.leaves(prunable), strict=True):
        if not p:
            continue
        assert t is not None, "tape missed a prunable leaf"
        assert j is not None, "jitted pass missed a prunable leaf"
        t, j = np.asarray(t, np.float64), np.asarray(j, np.float64)
        assert t.shape == j.shape, (t.shape, j.shape)
        worst = max(worst, float(np.linalg.norm(t - j) /
                                 (np.linalg.norm(t) + 1e-12)))
        checked += 1
    return worst, bool(worst <= tol) and checked > 0, checked


def _stack_chunk(batches: list[dict], start: int, length: int) -> dict:
    """Host-side stack of the next ``length`` calibration batches (cycled)."""
    sel = [batches[(start + j) % len(batches)] for j in range(length)]
    return jax.tree.map(
        lambda *xs: jnp.asarray(np.stack([np.asarray(x) for x in xs])), *sel)


def make_chunk_fn(pcfg: PruneConfig, loss_fn: Callable, stats: PyTree,
                  prunable: PyTree) -> Callable:
    """The search-chunk hot path: (state, stacked_batches) -> (state, ms).

    Exposed standalone so ``repro.analysis`` can register the exact function
    ``run_search`` jits (with ``donate_argnums=0``) as an audit surface -
    contract checks walk the jaxpr of THIS fn, not a lookalike.
    """
    def chunk_fn(st, stacked):
        return jax.lax.scan(
            lambda s, b: mirror.search_step(pcfg, loss_fn, s, b, stats,
                                            prunable),
            st, stacked)
    return chunk_fn


def run_search(cfg: ModelConfig, pcfg: PruneConfig, params0: PyTree,
               batches: list[dict], stats: PyTree, *,
               log_every: int = 0, loss_fn: Callable | None = None,
               rules=None, scan_chunk: int | None = None):
    """Returns (final state, history).

    The search runs as jitted ``lax.scan`` chunks of ``pcfg.scan_chunk``
    steps (override with ``scan_chunk``; <= 1 falls back to one dispatch
    per step) with the SearchState donated into each dispatch, so the three
    fp32 trees are updated in place instead of double-buffered.  With
    ``rules`` the state is placed via ``dist.sharding.search_state_sharding``
    and every chunk's stacked batches shard over the data axes - W/Gamma/V
    live distributed on the mesh for the whole search.
    """
    prunable = prunable_map(params0)
    loss_fn = loss_fn or partial(lm_loss, cfg)
    key = jax.random.key(17)
    if rules is None:
        state = mirror.init_search(params0, key)
    else:
        # built in place on the mesh: initialized on one device first, the
        # three fp32 trees would have to fit on it whole
        from repro.dist import sharding as sharding_mod
        from repro.models import model as M
        shapes = jax.eval_shape(mirror.init_search, params0, key)
        state = jax.jit(mirror.init_search, out_shardings=(
            sharding_mod.search_state_sharding(M.param_axes(cfg), shapes,
                                               rules)))(params0, key)
    batches = list(batches)
    chunk = pcfg.scan_chunk if scan_chunk is None else scan_chunk
    chunk = max(int(chunk), 0)
    history: list[dict] = []
    # series keys the flight recorder traces per chunk (convergence is the
    # paper's whole argument for global feedback - the trajectory must be
    # observable without re-running the search)
    _TRACE = ("loss", "align", "mask_churn", "gamma_entropy")

    def record(metrics_stack, start, length):
        """Fold one chunk's stacked metrics into history + the trace.

        Called with per-step metric arrays of shape (length,) - both the
        scanned path (real lax.scan outputs) and the eager path (a stack of
        one) land here, so logging and tracing behave identically.  Pulls
        to host exactly once per chunk, and only when someone is listening.
        """
        emit = obs.enabled()
        if not log_every and not emit:
            return
        host = {k: np.asarray(v) for k, v in metrics_stack.items()}
        if emit:
            sparsity = [float(1.0 - v) for v in host["gamma_nonzero_frac"]]
            obs.log("calibrate.search_chunk", start=start, steps=length,
                    sparsity=sparsity,
                    **{k: [float(x) for x in host[k]] for k in _TRACE
                       if k in host})
            obs.inc("calibrate.search_steps", length)
            obs.set_gauge("calibrate.gamma_entropy",
                          float(host["gamma_entropy"][-1]))
            obs.set_gauge("calibrate.mask_churn",
                          float(host["mask_churn"][-1]))
            obs.set_gauge("calibrate.sparsity", sparsity[-1])
        if log_every:
            for j in range(length):
                if (start + j) % log_every == 0:
                    history.append({k: float(v[j]) for k, v in host.items()})

    if chunk <= 1:  # eager: one jitted dispatch per step
        step_fn = jax.jit(
            lambda st, b: mirror.search_step(pcfg, loss_fn, st, b, stats,
                                             prunable),
            donate_argnums=0)
        for n in range(pcfg.steps):
            b = batches[n % len(batches)]
            recompile.note("search_step", (state, b))
            sp = obs.span("calibrate.search_step", step=n)
            with sp:
                state, m = step_fn(state, b)
                sp.fence(m)
            record({k: jnp.asarray(v)[None] for k, v in m.items()}, n, 1)
        return state, history

    chunk_jit = jax.jit(make_chunk_fn(pcfg, loss_fn, stats, prunable),
                        donate_argnums=0)
    n = 0
    while n < pcfg.steps:
        c = min(chunk, pcfg.steps - n)
        stacked = _stack_chunk(batches, n, c)
        if rules is not None:
            from repro.dist import sharding as sharding_mod
            stacked = jax.device_put(
                stacked,
                sharding_mod.stacked_batch_sharding(stacked, rules.mesh))
        recompile.note("search_chunk", (state, stacked))
        # fencing on the chunk's metric stack charges device time to the
        # chunk span; with the recorder off there is no fence and dispatch
        # stays fully async (record() then pulls nothing either)
        sp = obs.span("calibrate.search_chunk", start=n, steps=c)
        with sp:
            state, ms = chunk_jit(state, stacked)
            sp.fence(ms)
        record(ms, n, c)
        n += c
    return state, history


def unipruning_prune(cfg: ModelConfig, pcfg: PruneConfig, params0: PyTree,
                     calib_batches: list[dict],
                     sparsities: Iterable[float] = (0.5,),
                     loss_fn: Callable | None = None, *,
                     stats_impl: str = "jit", rules=None):
    """Full pipeline. Returns {sparsity: pruned_params}, Gamma, history."""
    stats = collect_stats(cfg, params0, calib_batches, pcfg=pcfg,
                          impl=stats_impl, rules=rules)
    state, history = run_search(cfg, pcfg, params0, calib_batches, stats,
                                log_every=10, loss_fn=loss_fn, rules=rules)
    out = {}
    for s in sparsities:
        masks = mirror.export_masks(pcfg, state.Gamma, s, V=state.V)
        out[s] = masks_mod.apply_masks(params0, masks)
    return out, state, history


def baseline_masks(method: str, params0: PyTree, stats: PyTree,
                   sparsity: float, *, mode: str = "unstructured",
                   scope: str = "row", nm: tuple[int, int] = (2, 4),
                   key: jax.Array | None = None) -> PyTree:
    """Local-metric one-shot baselines (no search stage)."""
    prunable = prunable_map(params0)
    S = metrics_mod.metric_tree(method, params0, stats, prunable, key=key)
    if mode == "nm":
        return masks_mod.nm_masks(S, *nm)
    if method == "magnitude" and scope == "row":
        scope = "layer"  # magnitude baseline is layer-wise in the paper
    return masks_mod.unstructured_masks(S, sparsity, scope=scope)
