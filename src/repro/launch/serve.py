"""Batched serving driver: prefill a batch of prompts, then decode tokens.

Demonstrates the serving path end-to-end on host devices, exercising the
same prefill/decode step functions the dry-run lowers for the production
mesh.  Sparse serving has two modes:

* ``--sparse [--save-artifact DIR]`` - run ``launch.calibrate`` (2:4) and
  serve from the resulting mask-bank artifact (written to --save-artifact,
  or a temp dir);
* ``--sparse-artifact DIR [--sparsity S]`` - skip calibration entirely:
  load the bank, re-threshold to masks in one shot, and serve with
  2:4-compressed weights executing through ``kernels.nm_spmm.nm_matmul``
  (``--weight-format masked`` serves the same masks as masked-dense W0*M -
  token-for-token identical, for A/B checks);
* ``--sparse-artifact DIR --fleet 0.0,0.5,2:4 [--ab W,W,...]`` - serve N
  budgets from the SAME bank concurrently behind one router
  (``serve.fleet.SparsityFleet``): tagged round-robin by default, weighted
  A/B traffic splitting with ``--ab`` (per-budget tok/s + token-agreement
  vs the densest member in the printed report);
* ``--fleet ... --spec draft:2:4,verify:0.0,k:4`` - route the batch
  through self-speculative decoding (``serve.spec``): the sparse member
  drafts k tokens per round, the dense member verifies them in one
  teacher-forced jitted pass; output bit-identical to the verifier alone.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --sparse --save-artifact results/bank/llama --gen 16
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --sparse-artifact results/bank/llama --gen 16
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --sparse-artifact results/bank/llama --fleet 0.0,0.5,2:4 --ab 1,1,2
  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --sparse-artifact results/bank/llama --fleet 0.0,2:4 \
      --spec draft:2:4,verify:0.0,k:4
"""
from __future__ import annotations

import argparse
import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.launch import compile_cache
from repro.configs.base import PruneConfig, get_config, get_smoke_config
from repro.data.synthetic import batches_for
from repro.models import model as M


def _step_annotation(name: str, step: int, annotate: bool):
    """StepTraceAnnotation mark when --xprof-dir captures, else nothing."""
    if not annotate:
        return contextlib.nullcontext()
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


def _calibrate_sparse(cfg, args, params):
    """2:4 UniPruning through the ``launch.calibrate`` entry point: the
    calibration always lands as a MaskBank artifact (a temp dir unless
    ``--save-artifact`` pins it) and serving re-thresholds from the bank -
    no inline stats/search in the serving driver."""
    import tempfile

    from repro.core import masks as masks_mod
    from repro.launch import calibrate as launch_cal
    tmp = None
    if args.save_artifact:
        out = args.save_artifact
    else:  # transient artifact: removed once the masks are extracted
        tmp = tempfile.TemporaryDirectory(prefix="mask-bank-")
        out = tmp.name + "/bank"
    try:
        calib = batches_for(cfg, n=8, batch=4, seq=args.prompt_len,
                            split="calib")
        pcfg = PruneConfig(local_metric="wanda", mode="nm", steps=30)
        bank = launch_cal.calibrate_to_bank(
            out, cfg=cfg, pcfg=pcfg, params=params, calib=calib,
            arch=args.arch, smoke=args.smoke)
        if args.save_artifact:
            print(f"saved mask bank -> {out}")
        print("serving 2:4-pruned weights (masked-dense, bank-backed "
              "calibration)")
        return masks_mod.apply_masks(params, bank.masks_at())
    finally:
        if tmp is not None:
            tmp.cleanup()


def _load_sparse(args, params):
    """Bank-backed sparse params: one-shot re-threshold, no calibration."""
    from repro.sparse.bank import MaskBank
    from repro.sparse.apply import compressed_report
    bank = MaskBank.load(args.sparse_artifact)
    # only the N:M pattern has a compressed execution format; an explicit
    # unstructured --sparsity re-threshold serves masked-dense
    compressed = (args.weight_format == "compressed"
                  and bank.pcfg.mode == "nm" and args.sparsity is None)
    if args.weight_format == "compressed" and not compressed:
        print("note: unstructured budget -> masked-dense serving "
              "(2:4-compressed execution needs the bank's N:M pattern)")
    sparse, masks = bank.sparse_params(params, sparsity=args.sparsity,
                                       compressed=compressed,
                                       idx_bits=args.idx_bits,
                                       with_masks=True)
    if compressed:
        rep = compressed_report(sparse, masks)
        n_comp = sum(not l["fallback"] for l in rep["layers"])
        print(f"serving from bank {args.sparse_artifact}: "
              f"{n_comp} kernels 2:4-compressed "
              f"({args.idx_bits}-bit index storage, "
              f"{rep['kernel_native_packed']} kernel-native packed planes, "
              f"{rep['fallback_leaves']} masked-dense fallbacks), "
              f"{rep['bytes_compressed'] / 1e6:.2f} MB vs "
              f"{rep['bytes_dense_bf16'] / 1e6:.2f} MB dense bf16 "
              f"(ratio {rep['ratio']:.3f})")
    else:
        print(f"serving from bank {args.sparse_artifact} (masked-dense)")
    return bank.cfg, sparse


def _serve_fleet(args, params) -> None:
    """N budgets from one bank behind one router; prints the A/B report."""
    from repro.serve.fleet import SparsityFleet
    budgets = [b for b in args.fleet.split(",") if b]
    capacity = args.prompt_len + args.gen + 1
    fleet = SparsityFleet.from_artifact(
        args.sparse_artifact, params, budgets, slots=args.slots,
        capacity=capacity, idx_bits=args.idx_bits, spec=args.spec)
    cfg = fleet.cfg
    batch = batches_for(cfg, n=1, batch=args.batch, seq=args.prompt_len,
                        split="valid")[0]
    prompts = [np.asarray(batch["tokens"][i]) for i in range(args.batch)]
    names = list(fleet.engines)
    if args.spec:
        rids = [fleet.submit(p, args.gen, spec=True) for p in prompts]
        print(f"self-speculative decoding: {args.spec}")
    elif args.ab:
        weights = [float(w) for w in args.ab.split(",")]
        if len(weights) != len(names):
            raise SystemExit(f"--ab needs {len(names)} weights (one per "
                             f"--fleet budget), got {len(weights)}")
        ab = dict(zip(names, weights))
        rids = [fleet.submit(p, args.gen, ab=ab) for p in prompts]
        print(f"A/B split over {names} with weights {weights}")
    else:
        rids = [fleet.submit(p, args.gen, budget=names[i % len(names)])
                for i, p in enumerate(prompts)]
        print(f"tagged round-robin over {names}")
    t0 = time.time()
    out = fleet.run()
    dt = time.time() - t0
    rep = fleet.report()
    print(f"fleet served {len(out)} requests x {args.gen} tokens from "
          f"{args.sparse_artifact} in {dt:.2f}s "
          f"(reference: {rep['reference']})")
    for name, r in rep["budgets"].items():
        agree = r["token_agreement_vs_reference"]
        p50, p95 = r["decode_ms_p50"], r["decode_ms_p95"]
        print(f"  {name:>6}: slots {r['slots']}, {r['requests']} reqs, "
              f"{(r['tok_s'] or 0):8.1f} tok/s, "
              f"byte ratio {r['weight_bytes_ratio']:.4f} "
              f"({r['compressed_kernels']} compressed, "
              f"{r['fallback_leaves']} masked-dense), "
              f"shared dense leaves {r['shared_dense_leaves']}"
              + (f", agreement vs ref {agree:.3f}" if agree is not None
                 else "")
              + (f", decode p50/p95 {p50:.2f}/{p95:.2f} ms"
                 if p50 is not None else ""))
    spec = rep["spec"]
    if spec is not None:
        print(f"  spec: {spec['draft']} drafts -> {spec['verify']} "
              f"verifies, k={spec['k']}, "
              f"accept rate {(spec['accept_rate'] or 0):.3f} "
              f"(EMA {spec['accept_ema']:.3f}), "
              f"{(spec['accepted_tokens_per_round'] or 0):.2f} tokens/round "
              f"over {spec['rounds']} rounds, "
              f"{spec['rollbacks']} rollbacks, "
              f"{(spec['tok_s'] or 0):.1f} tok/s")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--sparse", action="store_true",
                    help="prune 2:4 with UniPruning before serving")
    ap.add_argument("--save-artifact", default=None,
                    help="with --sparse: persist the mask bank here")
    ap.add_argument("--sparse-artifact", default=None,
                    help="serve from a saved mask bank (no calibration)")
    ap.add_argument("--sparsity", type=float, default=None,
                    help="unstructured budget for bank re-threshold "
                         "(default: the bank's calibrated N:M pattern)")
    ap.add_argument("--weight-format", default="compressed",
                    choices=["compressed", "masked"],
                    help="bank serving: 2:4-compressed kernels vs W0*M")
    ap.add_argument("--idx-bits", type=int, default=2, choices=[2, 8],
                    help="compressed index layout: 2 = packed 4-per-byte "
                         "(kernel-native, 9/16 of dense bf16 bytes), "
                         "8 = int8 fallback plane (3/4)")
    ap.add_argument("--fleet", default=None,
                    help="with --sparse-artifact: comma-separated budgets "
                         "served concurrently from the one bank behind one "
                         "router, e.g. 0.0,0.5,2:4")
    ap.add_argument("--ab", default=None,
                    help="with --fleet: comma-separated traffic weights "
                         "aligned with the --fleet budgets (default: "
                         "tagged round-robin)")
    ap.add_argument("--spec", default=None,
                    help="with --fleet: self-speculative decoding, e.g. "
                         "draft:2:4,verify:0.0,k:4 (draft member proposes "
                         "k tokens/round, verify member checks them in one "
                         "teacher-forced pass; lossless vs the verifier)")
    ap.add_argument("--slots", type=int, default=None,
                    help="fleet decode-slot pool partitioned across "
                         "budgets (default: 2 per budget)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--trace-dir", default=None,
                    help="enable the flight recorder and write the JSONL "
                         "event trace + a metrics.prom snapshot here")
    ap.add_argument("--xprof-dir", default=None,
                    help="capture a jax profiler trace here, with "
                         "StepTraceAnnotation marks per prefill/decode "
                         "step")
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.trace_dir:
        obs.configure(trace_dir=args.trace_dir)
    if args.xprof_dir:
        jax.profiler.start_trace(args.xprof_dir)
    try:
        _serve(args)
    finally:
        if args.xprof_dir:
            jax.profiler.stop_trace()
            print(f"wrote profiler trace -> {args.xprof_dir}")
        if args.trace_dir:
            import pathlib
            prom = pathlib.Path(args.trace_dir) / "metrics.prom"
            prom.write_text(obs.expose())
            obs.flush()
            print(f"wrote trace -> {obs.trace_path()} and {prom}")


def _serve(args) -> None:
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    assert not cfg.is_encoder_decoder or args.gen > 0
    params = M.init_params(cfg, jax.random.key(0))

    if args.spec and not args.fleet:
        raise SystemExit("--spec rides the fleet router: pass --fleet with "
                         "the draft and verify budgets")
    if args.fleet:
        if not args.sparse_artifact:
            raise SystemExit("--fleet serves from a saved mask bank: "
                             "pass --sparse-artifact DIR")
        _serve_fleet(args, params)
        return
    if args.sparse_artifact:
        cfg, params = _load_sparse(args, params)
    elif args.sparse:
        params = _calibrate_sparse(cfg, args, params)

    B, P = args.batch, args.prompt_len
    batch = batches_for(cfg, n=1, batch=B, seq=P, split="valid")[0]
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    capacity = P + args.gen + (cfg.num_image_tokens if cfg.vit_dim else 0)

    prefill = jax.jit(lambda p, b: M.prefill(cfg, p, b,
                                             cache_capacity=capacity))
    decode = jax.jit(lambda p, tok, c, t: M.decode_step(cfg, p, tok, c, t))

    xprof = bool(args.xprof_dir)
    # obs.timer: perf_counter + block_until_ready fencing on the stage
    # outputs - async dispatch is charged to the stage that launched it
    with _step_annotation("prefill", 0, xprof), \
            obs.timer("launch.prefill", batch=B, prompt_len=P) as tp:
        logits, caches = prefill(params, batch)
        toks = jnp.argmax(logits, axis=-1)
        tp.fence((toks, caches))
    out = [np.asarray(toks)]
    offset = cfg.num_image_tokens if cfg.vit_dim else 0
    with obs.timer("launch.decode", steps=args.gen - 1) as td:
        for i in range(args.gen - 1):
            sp = obs.span("serve.decode_step")
            with sp, _step_annotation("decode", i + 1, xprof):
                logits, caches = decode(params, toks, caches,
                                        jnp.asarray(P + offset + i,
                                                    jnp.int32))
                if args.temperature > 0:
                    key = jax.random.key(100 + i)
                    toks = jax.random.categorical(key,
                                                  logits / args.temperature)
                else:
                    toks = jnp.argmax(logits, axis=-1)
                out.append(np.asarray(toks))
            if sp.seconds is not None:
                obs.observe("serve.decode_step_ms", sp.seconds * 1e3)
        td.fence(toks)
    gen = np.stack(out, axis=1)
    print(f"prefill {B}x{P} in {tp.seconds:.2f}s; "
          f"decoded {args.gen - 1} steps in {td.seconds:.2f}s "
          f"({B * (args.gen - 1) / max(td.seconds, 1e-9):.1f} tok/s)")
    print("sample continuation:", gen[0][:16].tolist())


if __name__ == "__main__":
    main()
