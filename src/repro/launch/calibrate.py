"""Calibration entry point: stats -> mirror-descent search -> MaskBank.

The ONE place the UniPruning calibration pipeline runs.  Everything
downstream - ``launch.serve`` (single engine or ``--fleet``), the table
benchmarks, the examples - consumes the MaskBank artifact this writes and
never re-runs ``collect_stats`` / ``run_search`` inline: calibrate once,
re-threshold to masks at any budget, in any process.

The pipeline itself is the mesh-native one: the jitted sharded stats pass
(``models.model.stats_sumsq``), then ``lax.scan``-chunked jitted search
steps with donated, ``dist.sharding``-placed state (pass ``--mesh`` /
``rules=``), with optional microbatch gradient accumulation
(``--grad-accum``).

  PYTHONPATH=src python -m repro.launch.calibrate --arch llama3.2-1b \
      --smoke --out results/bank/llama --metric wanda --mode nm --steps 30
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --sparse-artifact results/bank/llama --fleet 0.0,0.5,2:4
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
from typing import Any

import jax

from repro import obs
from repro.launch import compile_cache
from repro.configs.base import PruneConfig, get_config, get_smoke_config

PyTree = Any


def _stage_annotation(name: str, step: int, annotate: bool):
    """jax.profiler.StepTraceAnnotation when --xprof-dir is live, else a
    nullcontext - the annotations only mean something inside an active
    profiler trace."""
    if not annotate:
        return contextlib.nullcontext()
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


def params_fingerprint(params: PyTree) -> str:
    """Order-stable crc32 of the weights a bank was calibrated against."""
    from repro.sparse.bank import _tree_checksum
    return _tree_checksum(params)


def calibrate_to_bank(out_dir, *, cfg, pcfg: PruneConfig, params: PyTree,
                      calib: list[dict], arch: str, smoke: bool,
                      rules=None, stats_impl: str = "jit",
                      log_every: int = 0, loss_fn=None,
                      extra: dict | None = None, xprof: bool = False):
    """Run the full calibration once and persist the MaskBank artifact.

    Returns the in-memory :class:`~repro.sparse.bank.MaskBank` backed by the
    artifact just written to ``out_dir``.

    Stage timings go through ``obs.timer``: monotonic ``perf_counter``
    clocks with ``jax.block_until_ready`` fencing on each stage's outputs,
    so the seconds recorded in the bank's meta measure the device work the
    stage dispatched, not just the python that launched it (a bare
    ``time.time()`` around async-dispatched jax under-reports and bills
    the tail to the next stage).  ``xprof=True`` wraps each stage in a
    ``jax.profiler.StepTraceAnnotation`` for an active profiler trace.
    """
    from repro.core import calibrate
    from repro.sparse.bank import MaskBank
    with _stage_annotation("calibrate.stats", 0, xprof), \
            obs.timer("calibrate.stats", arch=arch,
                      stats_impl=stats_impl) as t_stats:
        stats = calibrate.collect_stats(cfg, params, calib, pcfg=pcfg,
                                        impl=stats_impl, rules=rules)
        t_stats.fence(stats)
    with _stage_annotation("calibrate.search", 1, xprof), \
            obs.timer("calibrate.search", arch=arch,
                      steps=pcfg.steps) as t_search:
        state, history = calibrate.run_search(cfg, pcfg, params, calib,
                                              stats, rules=rules,
                                              log_every=log_every,
                                              loss_fn=loss_fn)
        t_search.fence(state)
    meta = {"params_fingerprint": params_fingerprint(params),
            "stats_impl": stats_impl,
            "stats_seconds": t_stats.seconds,
            "search_seconds": t_search.seconds,
            "history": history, **(extra or {})}
    with obs.timer("calibrate.save_bank", arch=arch) as t_save:
        bank = MaskBank.save(out_dir, arch=arch, smoke=smoke, state=state,
                             stats=stats, pcfg=pcfg, cfg=cfg, extra=meta)
    obs.log("calibrate.done", arch=arch, out_dir=str(out_dir),
            stats_seconds=t_stats.seconds, search_seconds=t_search.seconds,
            save_seconds=t_save.seconds)
    return bank


def ensure_bank(out_dir, *, cfg, pcfg: PruneConfig, params: PyTree,
                calib: list[dict], arch: str, smoke: bool, **kw):
    """Load the bank at ``out_dir`` if it matches (same PruneConfig, same
    weights fingerprint); otherwise calibrate and (re)write it.  The cache
    that lets many benchmark tables share ONE calibration per model."""
    from repro.sparse.bank import MaskBank
    try:
        bank = MaskBank.load(out_dir, cfg=cfg)
        if (bank.meta.get("pcfg") == dataclasses.asdict(pcfg)
                and bank.meta.get("params_fingerprint")
                == params_fingerprint(params)):
            return bank
    except (FileNotFoundError, ValueError, AssertionError, KeyError):
        pass  # absent/stale/corrupt bank: fall through and recalibrate
    return calibrate_to_bank(out_dir, cfg=cfg, pcfg=pcfg, params=params,
                             calib=calib, arch=arch, smoke=smoke, **kw)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", required=True, help="mask-bank artifact dir")
    ap.add_argument("--metric", default="wanda",
                    choices=["magnitude", "wanda", "ria", "stochria"])
    ap.add_argument("--mode", default="nm",
                    choices=["nm", "unstructured"])
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--stats-batches", type=int, default=4)
    ap.add_argument("--scan-chunk", type=int, default=8,
                    help="search steps per jitted lax.scan dispatch "
                         "(<= 1: eager per-step dispatch)")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="microbatches per search step (gradient "
                         "accumulation over batch-dim slices)")
    ap.add_argument("--stats-impl", default="jit", choices=["jit", "tape"])
    ap.add_argument("--calib-n", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh", default=None, choices=[None, "host"],
                    help="'host': shard stats + search state over the "
                         "local host mesh via dist.sharding rules")
    ap.add_argument("--trace-dir", default=None,
                    help="enable the flight recorder and write the JSONL "
                         "event trace (spans, per-chunk search series) + "
                         "a metrics.prom snapshot here")
    ap.add_argument("--xprof-dir", default=None,
                    help="capture a jax profiler trace here, with "
                         "StepTraceAnnotation marks per pipeline stage")
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.trace_dir:
        obs.configure(trace_dir=args.trace_dir)

    from repro.data.synthetic import batches_for
    from repro.models import model as M
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    calib = batches_for(cfg, n=args.calib_n, batch=args.batch, seq=args.seq,
                        split="calib")
    pcfg = PruneConfig(local_metric=args.metric, mode=args.mode,
                       steps=args.steps, stats_batches=args.stats_batches,
                       scan_chunk=args.scan_chunk,
                       grad_accum=args.grad_accum)
    rules = None
    if args.mesh == "host":
        from repro.dist.sharding import (init_params_sharded,
                                         make_production_rules)
        from repro.launch.mesh import make_host_mesh
        rules = make_production_rules(make_host_mesh())
        params = init_params_sharded(cfg, jax.random.key(0), rules)
    else:
        params = M.init_params(cfg, jax.random.key(0))

    if args.xprof_dir:
        jax.profiler.start_trace(args.xprof_dir)
    try:
        bank = calibrate_to_bank(args.out, cfg=cfg, pcfg=pcfg,
                                 params=params, calib=calib, arch=args.arch,
                                 smoke=args.smoke, rules=rules,
                                 stats_impl=args.stats_impl,
                                 log_every=args.log_every,
                                 xprof=bool(args.xprof_dir))
    finally:
        if args.xprof_dir:
            jax.profiler.stop_trace()
            print(f"wrote profiler trace -> {args.xprof_dir}")
    n_pr = sum(g.size for g in jax.tree.leaves(
        bank.Gamma, is_leaf=lambda x: x is None) if g is not None)
    print(f"calibrated {args.arch}{' (smoke)' if args.smoke else ''}: "
          f"{pcfg.steps} search steps over {n_pr/1e6:.2f}M prunable params "
          f"(stats {bank.meta['stats_seconds']:.1f}s via "
          f"{args.stats_impl}, search {bank.meta['search_seconds']:.1f}s, "
          f"{pcfg.steps / max(bank.meta['search_seconds'], 1e-9):.2f} "
          f"steps/s)")
    print(f"saved mask bank -> {args.out}")
    if args.trace_dir:
        import pathlib
        prom = pathlib.Path(args.trace_dir) / "metrics.prom"
        prom.write_text(obs.expose())
        obs.flush()
        print(f"wrote trace -> {obs.trace_path()} and {prom}")


if __name__ == "__main__":
    main()
