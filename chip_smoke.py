#!/usr/bin/env python3
"""On-chip smoke run of the main path at llama3.2-1b's published widths.

  python chip_smoke.py               # one TPU chip (the default)
  python chip_smoke.py --four-chips  # four chips of one host

One process drives every phase (a chip belongs to one process at a time, so
nothing here starts a child).  Weights and data come from ``--seed``; the
mask bank is written under ``--out`` and nothing is read from ``results/``.

One chip, in order:

1. device    - the first device is a TPU (no CPU fallback);
2. kernels   - ``nm_matmul`` at the llama projection shapes for decode
               (M=4) and prefill (M=512) and ``nm_matmul_expert`` at one
               mixtral-8x22b expert slice, each against
               ``kernels.ref.nm_matmul_ref`` in float32, and
               ``flash_decode_partial`` at one capacity shard of the
               four-chip decode against its float32 oracle;
3. calibrate - ``launch.calibrate.calibrate_to_bank`` (nm, wanda) at full
               width, depth cut to what one chip's search holds, sized from
               ``memory_analysis()`` of the compiled search chunk;
4. fleet     - ``SparsityFleet.from_artifact`` over budgets 0.0, 0.5 and
               2:4 answers 8 requests; the 2:4 member has no masked-dense
               fallback, its decode program holds the Pallas kernel, and
               its prefill logits match the same masks served masked-dense;
5. full      - all 16 layers: ``launch.serve.main`` with dense weights,
               then a ``ServeEngine`` on 2:4-compressed weights (jitted
               stats, wanda 2:4 masks, ``sparsify_params``).

Four chips: full-depth calibration with the params and the search state
created sharded over a (4, 1) mesh, then the bank's 2:4 member served
tensor-parallel on a (1, 4) mesh and compared with the same weights served
on one device: every generated token, and the logits of the prefill and of
one decode step (which runs the capacity-sharded flash attention); the
per-site psum counts are printed.

Every phase prints one line with its shapes, checks, seconds and compile
seconds.  Any failure exits non-zero and prints no result; the last line of
a passing run is ``{"ok": true, "device": {...}}``.  The compile cache goes
where ``JAX_COMPILATION_CACHE_DIR`` says, else to ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import dataclasses
import gc
import io
import json
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "llama3.2-1b"

# kernel vs float32 reference: products of bf16 operands are exact in the
# f32 accumulator, so only the summation order differs
KERNEL_RTOL = 1e-3
# flash decode partial (acc, m, l) vs its float32 oracle: bf16 operands make
# q.k exact; the bound covers one bf16 pass over the float32 probabilities
# in the PV product (2**-8 relative per term)
FLASH_RTOL = 1e-2
# compressed vs masked-dense logits (both bf16 activations; the GEMMs sum
# in different orders): relative Frobenius error
LOGITS_RTOL = 5e-2
# share of the device's memory the calibration search may plan for
SEARCH_MEM_SHARE = 0.85


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileMeter:
    """Seconds of XLA backend compilation and persistent-cache hits, from
    jax.monitoring events (a cache hit skips the backend compile)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@dataclasses.dataclass
class Sizes:
    """What the phases run at (the defaults are the chip run's)."""
    cfg: object                      # full-depth ModelConfig
    calib_batches: int = 2
    calib_batch: int = 4
    calib_seq: int = 128
    steps: int = 8
    scan_chunk: int = 4
    requests: int = 8
    prompt: int = 128
    gen: int = 32
    full_gen: int = 16
    full_batch: int = 4
    capacity: int = 256
    kernel_m: tuple = (4, 512)
    expert: tuple = (2, 8, 6144, 16384)   # E, M, K, N (mixtral-8x22b)


class Run:
    def __init__(self, sizes: Sizes, out: pathlib.Path, seed: int, meter):
        self.s = sizes
        self.out = out
        self.seed = seed
        self.meter = meter

    def phase(self, name: str, fn) -> None:
        t0 = time.perf_counter()
        c0, h0 = self.meter.seconds, self.meter.hits
        detail = fn()
        dt = time.perf_counter() - t0
        print(f"[{name}] ok in {dt:.1f}s (compile "
              f"{self.meter.seconds - c0:.1f}s, cache hits "
              f"{self.meter.hits - h0}): {detail}", flush=True)

    # -- shared helpers ------------------------------------------------------

    def calib(self, cfg):
        from repro.data.synthetic import batches_for
        s = self.s
        return batches_for(cfg, n=s.calib_batches, batch=s.calib_batch,
                           seq=s.calib_seq, split="calib", seed=self.seed)

    def pcfg(self):
        from repro.configs.base import PruneConfig
        s = self.s
        return PruneConfig(local_metric="wanda", mode="nm", steps=s.steps,
                           stats_batches=s.calib_batches,
                           scan_chunk=s.scan_chunk)

    def prompts(self, cfg, n: int, seq: int):
        import numpy as np
        from repro.data.synthetic import batches_for
        toks = batches_for(cfg, n=1, batch=n, seq=seq, split="valid",
                           seed=self.seed)[0]["tokens"]
        return [np.asarray(t) for t in toks]

    def params(self, cfg):
        import jax
        from repro.models import model as M
        return M.init_params(cfg, jax.random.key(self.seed))

    # -- phase 2: kernels ----------------------------------------------------

    def kernels(self) -> str:
        import jax
        import jax.numpy as jnp
        from repro.kernels import ref
        from repro.kernels.nm_spmm import nm_matmul, nm_matmul_expert
        from repro.sparse.formats import _pack_idx2
        cfg = self.s.cfg
        h = cfg.num_heads * cfg.head_dim
        kv = cfg.num_kv_heads * cfg.head_dim
        shapes = sorted({(cfg.d_model, h), (cfg.d_model, kv),
                         (h, cfg.d_model), (cfg.d_model, cfg.d_ff),
                         (cfg.d_ff, cfg.d_model)})
        key = jax.random.key(self.seed)
        worst = 0.0

        def compare(y, r, what):
            nonlocal worst
            err = float(jnp.max(jnp.abs(y - r)) /
                        (jnp.max(jnp.abs(r)) + 1e-30))
            check(bool(jnp.isfinite(y).all()), f"{what}: non-finite output")
            check(err <= KERNEL_RTOL,
                  f"{what}: max error {err:.2e} of max |ref| > {KERNEL_RTOL}")
            worst = max(worst, err)

        def operands(k, shape):
            w = jax.random.normal(k, shape, jnp.float32)
            compress = ref.compress_24
            for _ in range(len(shape) - 2):
                compress = jax.vmap(compress)
            vals, idx = compress(w)
            return vals.astype(jnp.bfloat16), idx

        for i, (K, N) in enumerate(shapes):
            vals, idx = operands(jax.random.fold_in(key, i), (K, N))
            packed = _pack_idx2(idx)
            for M in self.s.kernel_m:
                x = jax.random.normal(jax.random.fold_in(key, 100 + i),
                                      (M, K)).astype(jnp.bfloat16)
                y = nm_matmul(x, vals, packed, bm=min(M, 128),
                              bk=min(K, 512), bn=min(N, 256),
                              out_dtype=jnp.float32)
                with jax.default_matmul_precision("highest"):
                    r = ref.nm_matmul_ref(x.astype(jnp.float32),
                                          vals.astype(jnp.float32), idx)
                compare(y, r, f"nm_matmul M={M} K={K} N={N}")
        E, M, K, N = self.s.expert
        vals, idx = operands(jax.random.fold_in(key, 999), (E, K, N))
        x = jax.random.normal(jax.random.fold_in(key, 1000),
                              (E, M, K)).astype(jnp.bfloat16)
        y = nm_matmul_expert(x, vals, _pack_idx2(idx), bm=M,
                             bk=min(K, 512), bn=min(N, 256),
                             out_dtype=jnp.float32)
        with jax.default_matmul_precision("highest"):
            r = jnp.stack([ref.nm_matmul_ref(x[e].astype(jnp.float32),
                                             vals[e].astype(jnp.float32),
                                             idx[e]) for e in range(E)])
        compare(y, r, f"nm_matmul_expert E={E} M={M} K={K} N={N}")
        fd = self.flash_partial(key)
        return (f"nm_matmul packed2 at (K, N) {shapes} x M {self.s.kernel_m}"
                f" and nm_matmul_expert (E, M, K, N) {self.s.expert} match "
                f"the f32 reference (worst {worst:.2e} of max |ref|, "
                f"limit {KERNEL_RTOL}); {fd}")

    def flash_partial(self, key) -> str:
        """``flash_decode_partial`` at one capacity shard of the four-chip
        decode (B x capacity/4 rows, every KV head) against its f32 oracle,
        with one row all masked as a shard past the prompt is."""
        import jax
        import jax.numpy as jnp
        from repro.kernels.flash_decode import (flash_decode_partial,
                                                flash_decode_partial_ref)
        cfg = self.s.cfg
        B, C = self.s.full_batch, self.s.capacity // 4
        K, D = cfg.num_kv_heads, cfg.head_dim
        G = cfg.num_heads // K
        ks = jax.random.split(jax.random.fold_in(key, 2000), 3)
        q = jax.random.normal(ks[0], (B, K, G, D)).astype(jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, C, K, D)).astype(jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, C, K, D)).astype(jnp.bfloat16)
        valid = jnp.arange(C)[None, :] < jnp.array([C, C // 2, 1, 0])[:, None]
        bias = jnp.where(valid, 0.0, -1e30).astype(jnp.float32)
        got = flash_decode_partial(q, k, v, bias)
        with jax.default_matmul_precision("highest"):
            want = flash_decode_partial_ref(q, k, v, bias)
        errs = []
        for g, w, name in zip(got, want, ("acc", "m", "l")):
            check(bool(jnp.isfinite(g).all()), f"flash {name}: non-finite")
            err = float(jnp.max(jnp.abs(g - w)) /
                        (jnp.max(jnp.abs(w)) + 1e-30))
            check(err <= FLASH_RTOL, f"flash_decode_partial {name}: max "
                  f"error {err:.2e} of max |ref| > {FLASH_RTOL}")
            errs.append(f"{name} {err:.2e}")
        return (f"flash_decode_partial (B, C, K, G, D) {(B, C, K, G, D)} "
                f"with rows valid to {[C, C // 2, 1, 0]} matches the f32 "
                f"oracle ({', '.join(errs)} of max |ref|, limit "
                f"{FLASH_RTOL})")

    # -- phase 3: calibrate --------------------------------------------------

    def search_depth(self) -> tuple[int, str]:
        """Layers one chip's search holds: compile the search chunk at two
        depths, fit resident bytes (chunk peak + the fp32 params it is
        calibrated from) linearly in depth, cut at the device's limit."""
        import jax
        import jax.numpy as jnp
        from repro.core import calibrate as cal
        from repro.core import mirror
        from repro.core.prunable import prunable_map
        from repro.models import model as M
        from repro.optim.losses import lm_loss
        full = self.s.cfg
        pcfg = self.pcfg()

        def resident(L):
            cfg = dataclasses.replace(full, num_layers=L)
            p = jax.eval_shape(lambda: self.params(cfg))
            state = jax.eval_shape(
                lambda q: mirror.init_search(q, jax.random.key(17)), p)
            b = {"tokens": jax.ShapeDtypeStruct(
                (self.s.calib_batch, self.s.calib_seq), jnp.int32)}
            stats = jax.eval_shape(lambda q, bb: M.stats_sumsq(cfg, q, bb),
                                   p, b)
            stacked = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct((pcfg.scan_chunk, *a.shape),
                                               a.dtype), b)
            loss = lambda w, bb: lm_loss(cfg, w, bb)

            def chunk(st, stk, stats_):
                return cal.make_chunk_fn(pcfg, loss, stats_,
                                         prunable_map(p))(st, stk)
            ma = jax.jit(chunk, donate_argnums=0).lower(
                state, stacked, stats).compile().memory_analysis()
            peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                    - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
            params_b = sum(a.size * a.dtype.itemsize
                           for a in jax.tree.leaves(p))
            return peak + params_b

        # the two compiles are independent: run them side by side
        with concurrent.futures.ThreadPoolExecutor(2) as pool:
            r2, r4 = pool.map(resident, (2, 4))
        per_layer = (r4 - r2) / 2
        base = r2 - 2 * per_layer
        limit = jax.devices()[0].memory_stats()["bytes_limit"]
        depth = int((SEARCH_MEM_SHARE * limit - base) // per_layer)
        depth = max(1, min(full.num_layers, depth))
        return depth, (f"search resident {base / 1e9:.2f} GB + "
                       f"{per_layer / 1e9:.3f} GB/layer against "
                       f"{SEARCH_MEM_SHARE} x {limit / 1e9:.2f} GB")

    def calibrate(self, cfg, rules=None):
        """calibrate_to_bank at ``cfg``; returns (bank, params, bank dir)."""
        import numpy as np
        from repro.launch.calibrate import calibrate_to_bank
        if rules is None:
            params = self.params(cfg)
        else:  # built in place on the mesh, never whole on one device
            import jax
            from repro.dist.sharding import init_params_sharded
            params = init_params_sharded(cfg, jax.random.key(self.seed),
                                         rules)
        out = self.out / f"bank-{cfg.num_layers}l"
        bank = calibrate_to_bank(out, cfg=cfg, pcfg=self.pcfg(),
                                 params=params, calib=self.calib(cfg),
                                 arch=ARCH, smoke=False, rules=rules)
        masks = bank.masks_at()
        n_masks = 0
        for m in (x for x in _leaves(masks) if x is not None):
            g = np.asarray(m).reshape(*m.shape[:-2], m.shape[-2] // 4, 4,
                                      m.shape[-1])
            check(bool((g.sum(-2) == 2).all()), "bank mask is not 2:4")
            n_masks += 1
        check(n_masks > 0, "bank exported no masks")
        check(int(bank.meta["steps_run"]) == self.s.steps,
              f"search ran {bank.meta['steps_run']} of {self.s.steps} steps")
        return bank, params, out, n_masks

    # -- phase 4: fleet ------------------------------------------------------

    def fleet(self, cfg, params, bank_dir) -> str:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro.models import model as M
        from repro.serve.fleet import SparsityFleet
        s = self.s
        fleet = SparsityFleet.from_artifact(
            bank_dir, params, ["0.0", "0.5", "2:4"], cfg=cfg,
            capacity=s.capacity)
        names = list(fleet.engines)
        prompts = self.prompts(cfg, s.requests, s.prompt)
        rids = [fleet.submit(p, s.gen, budget=names[i % len(names)])
                for i, p in enumerate(prompts)]
        res = fleet.run()
        lens = [len(res[r]) for r in rids]
        check(lens == [s.gen] * s.requests, f"token counts {lens}")
        check(all(0 <= t < cfg.vocab_size for r in rids for t in res[r]),
              "token id out of vocabulary")
        rep = fleet.reports["2:4"]
        check(rep["fallback_leaves"] == 0,
              f"2:4 member has {rep['fallback_leaves']} masked-dense "
              "fallbacks")
        check(rep["compressed_kernels"] > 0, "2:4 member compressed nothing")
        eng = fleet.engines["2:4"]
        hlo = fleet.fns.decode.lower(
            eng.params, jnp.zeros((eng.slots,), jnp.int32), eng.caches,
            jnp.zeros((eng.slots,), jnp.int32)).compile().as_text()
        n_kernel = hlo.count("tpu_custom_call")
        check(n_kernel > 0, "2:4 decode program holds no Pallas kernel")
        masked = fleet.bank.sparse_params(params, nm=(2, 4), compressed=False)
        toks = jnp.asarray(np.stack(prompts[:2]))
        fwd = jax.jit(lambda p, t: M.prefill(cfg, p, {"tokens": t},
                                             cache_capacity=s.capacity)[0])
        lg_c = np.asarray(fwd(eng.params, toks), np.float32)
        lg_m = np.asarray(fwd(masked, toks), np.float32)
        check(bool(np.isfinite(lg_c).all()), "non-finite compressed logits")
        err = float(np.linalg.norm(lg_c - lg_m) / np.linalg.norm(lg_m))
        agree = float((lg_c.argmax(-1) == lg_m.argmax(-1)).mean())
        check(err <= LOGITS_RTOL,
              f"2:4 prefill logits off masked-dense by {err:.2e}")
        return (f"{s.requests} requests x {s.gen} tokens over {names} "
                f"(prompt {s.prompt}); 2:4 member: "
                f"{rep['compressed_kernels']} compressed kernels, 0 "
                f"fallbacks, byte ratio {rep['weight_bytes_ratio']:.4f}, "
                f"{n_kernel} tpu_custom_call in its decode HLO; prefill "
                f"logits {lg_c.shape} vs masked-dense: rel err {err:.2e} "
                f"(limit {LOGITS_RTOL}), argmax agreement {agree:.3f}")

    # -- phase 5: full depth -------------------------------------------------

    def full_dense(self) -> str:
        from repro.launch import serve
        s = self.s
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            serve.main(["--arch", ARCH, "--batch", str(s.full_batch),
                        "--prompt-len", str(s.prompt),
                        "--gen", str(s.full_gen)])
        lines = buf.getvalue().strip().splitlines()
        sample = next((ln for ln in lines
                       if ln.startswith("sample continuation:")), None)
        check(sample is not None, f"launch.serve printed {lines}")
        toks = json.loads(sample.split(":", 1)[1])
        check(len(toks) == s.full_gen and
              all(0 <= t < s.cfg.vocab_size for t in toks),
              f"dense continuation {toks}")
        return (f"launch.serve.main dense {s.cfg.num_layers} layers, batch "
                f"{s.full_batch} x prompt {s.prompt} + {s.full_gen} tokens: "
                f"{sample}")

    def full_compressed(self) -> str:
        import jax
        import jax.numpy as jnp
        from repro.core import calibrate as cal
        from repro.models import model as M
        from repro.serve.engine import ServeEngine
        from repro.sparse.apply import compressed_report, sparsify_params
        s = self.s
        cfg = s.cfg
        params = self.params(cfg)
        stats = cal.collect_stats(cfg, params, self.calib(cfg))
        # jitted so the per-leaf score and rank temporaries fuse: run op by
        # op, a (layers, K/4, 4, 4, N) compare does not fit beside the
        # full-depth fp32 params
        masks = jax.jit(lambda p, st: cal.baseline_masks(
            "wanda", p, st, 0.5, mode="nm"))(params, stats)
        sparse = sparsify_params(params, masks, axes=M.param_axes(cfg),
                                 dtype=jnp.bfloat16)
        rep = compressed_report(sparse, masks)
        del params, masks, stats
        gc.collect()
        check(rep["fallback_leaves"] == 0,
              f"{rep['fallback_leaves']} masked-dense fallbacks")
        eng = ServeEngine(cfg, sparse, slots=s.full_batch,
                          capacity=s.capacity)
        prompts = self.prompts(cfg, s.full_batch, s.prompt)
        rids = [eng.submit(p, s.full_gen) for p in prompts]
        res = eng.run()
        lens = [len(res[r]) for r in rids]
        check(lens == [s.full_gen] * s.full_batch, f"token counts {lens}")
        return (f"ServeEngine {cfg.num_layers} layers on 2:4-compressed "
                f"weights ({len(rep['layers'])} kernels, 0 fallbacks, byte "
                f"ratio {rep['ratio']:.4f}): {s.full_batch} requests x "
                f"{s.full_gen} tokens, first {res[rids[0]][:8]}")

    # -- four chips ----------------------------------------------------------

    def four_chips(self) -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from repro import obs
        from repro.dist import sharding as shd
        from repro.dist.axes import make_rules, use_rules
        from repro.launch.mesh import make_host_mesh, make_mesh
        from repro.models import model as M
        from repro.serve.engine import ServeEngine
        s = self.s
        cfg = s.cfg
        state = {}

        def calibrate():
            mesh = make_host_mesh()
            bank, params, out, n_masks = self.calibrate(
                cfg, rules=shd.make_production_rules(mesh))
            g = next(x for x in _leaves(bank.Gamma) if x is not None)
            check(len(g.sharding.device_set) == len(jax.devices()),
                  f"search state on {len(g.sharding.device_set)} devices")
            state.update(bank=bank, params=params)
            return (f"{cfg.num_layers} layers on mesh {dict(mesh.shape)}, "
                    f"{n_masks} 2:4 masks -> {out}; a Gamma leaf "
                    f"{g.shape} is {g.sharding.spec}; "
                    + _memory_line())

        self.phase("calibrate-4chip", calibrate)

        def serve_tp():
            bank, params = state.pop("bank"), state.pop("params")
            sparse = bank.sparse_params(params, nm=(2, 4))
            del bank, params
            gc.collect()
            one = jax.devices()[0]
            rep_params = jax.device_put(sparse, one)
            mesh = make_mesh((1, 4), ("data", "model"))
            rules = make_rules(mesh)
            obs.configure(enabled=True)
            prompts = self.prompts(cfg, s.full_batch, s.prompt)
            out, logits = {}, {}
            for name, p, r in (("tp", sparse, rules),
                               ("replicated", rep_params, None)):
                eng = ServeEngine(cfg, p, slots=s.full_batch,
                                  capacity=s.capacity, rules=r)
                rids = [eng.submit(q, s.full_gen) for q in prompts]
                res = eng.run()
                out[name] = [res[i] for i in rids]
                if name == "tp":  # counted once per traced program
                    psums = {site: obs.counter_value("dist.psum", site=site)
                             for site in ("mlp", "attn", "attn_kv", "moe")}

                def two_steps(q, t):
                    """Prefill logits and one decode step's logits; the
                    decode token is fixed (the prompts' first column) so
                    both engines attend from the same input."""
                    lg, caches = M.prefill(cfg, q, {"tokens": t},
                                           cache_capacity=s.capacity)
                    lg2, _ = M.decode_step(cfg, q, t[:, 0], caches,
                                           jnp.int32(t.shape[1]))
                    return lg, lg2

                with (use_rules(r) if r is not None
                      else contextlib.nullcontext()):
                    logits[name] = [np.asarray(x, np.float32) for x in
                                    jax.jit(two_steps)(
                                        eng.params,
                                        jnp.asarray(np.stack(prompts)))]
                if name == "tp":
                    leaf = next(x for x in _leaves(eng.params)
                                if hasattr(x, "vals"))
                    placed = (f"a compressed leaf's vals {leaf.vals.shape} "
                              f"is {leaf.vals.sharding.spec}")
                del eng
            check(psums["mlp"] > 0 and psums["attn"] > 0,
                  f"tensor-parallel path ran no psum: {psums}")
            check(psums["attn_kv"] > 0, "TP decode did not run the "
                  f"capacity-sharded flash attention: {psums}")
            errs = []
            for what, a, b in zip(("prefill", "decode"), logits["tp"],
                                  logits["replicated"]):
                err = float(np.linalg.norm(a - b) / np.linalg.norm(b))
                check(bool(np.isfinite(a).all()), f"non-finite TP {what}")
                check(err <= LOGITS_RTOL,
                      f"TP {what} logits off replicated by {err:.2e}")
                errs.append(err)
            check([len(o) for o in out["tp"]] == [s.full_gen] * s.full_batch,
                  "TP engine token counts")
            flat_a = [t for o in out["tp"] for t in o]
            flat_b = [t for o in out["replicated"] for t in o]
            agree = float(np.mean(np.equal(flat_a, flat_b)))
            check(agree == 1.0, f"TP tokens agree with replicated on "
                  f"{agree:.3f} of positions")
            return (f"2:4 bank member on mesh {dict(mesh.shape)} vs one "
                    f"device: {s.full_batch} requests x {s.full_gen} tokens,"
                    f" token agreement {agree:.3f}; logits rel err prefill "
                    f"{errs[0]:.2e}, first decode step {errs[1]:.2e} (limit "
                    f"{LOGITS_RTOL}); psums per site over the TP prefill + "
                    f"decode programs {psums}; {placed}; " + _memory_line())

        self.phase("serve-tp-4chip", serve_tp)


def _leaves(tree):
    import jax
    from repro.sparse.formats import SparseTensor
    return jax.tree.leaves(
        tree, is_leaf=lambda x: x is None or isinstance(x, SparseTensor))


def _memory_line() -> str:
    import jax
    used = []
    for d in jax.devices():
        st = d.memory_stats() or {}
        used.append(f"{st.get('peak_bytes_in_use', 0) / 1e9:.2f}")
    return f"peak GB per device {used}"


def run(args, meter) -> None:
    import jax
    from repro.configs.base import get_config
    from repro.launch import compile_cache
    cache = compile_cache.enable()
    n = len(jax.devices())
    print(f"[device] {jax.devices()[0].device_kind} x {n}, jax "
          f"{jax.__version__}, libtpu {_libtpu_version()}, compile cache "
          f"{cache}", flush=True)
    cfg = get_config(ARCH)
    args.out.mkdir(parents=True, exist_ok=True)
    r = Run(Sizes(cfg=cfg), args.out, args.seed, meter)
    if args.four_chips:
        r.four_chips()
        return
    r.phase("kernels", r.kernels)
    state = {}

    def calibrate():
        depth, why = r.search_depth()
        cut = dataclasses.replace(cfg, num_layers=depth)
        bank, params, out, n_masks = r.calibrate(cut)
        state.update(cfg=cut, params=params, out=out)
        return (f"depth cut to {depth} of {cfg.num_layers} layers ({why}); "
                f"widths d_model {cfg.d_model}, heads {cfg.num_heads}/"
                f"{cfg.num_kv_heads}, d_ff {cfg.d_ff}, vocab "
                f"{cfg.vocab_size}; {r.s.steps} steps, {n_masks} 2:4 masks "
                f"-> {out}; stats {bank.meta['stats_seconds']:.1f}s, search "
                f"{bank.meta['search_seconds']:.1f}s; " + _memory_line())

    r.phase("calibrate", calibrate)
    r.phase("fleet", lambda: r.fleet(state["cfg"], state["params"],
                                     state["out"]))
    state.clear()
    gc.collect()
    r.phase("full-dense", r.full_dense)
    gc.collect()
    r.phase("full-compressed", r.full_compressed)


def _libtpu_version() -> str:
    from importlib import metadata
    try:
        return metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # printed on the device line
        return "not installed"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip path (sharded "
                         "calibration + tensor-parallel 2:4 serving)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=pathlib.Path,
                    default=ROOT / ".chip_smoke",
                    help="where the mask banks go (git-ignored; a "
                         "full-width bank is gigabytes)")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"FAIL: no TPU: jax found {platform!r} devices", flush=True)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"FAIL: {need} chips needed, {len(devices)} found", flush=True)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    meter = CompileMeter()
    t0 = time.perf_counter()
    try:
        run(args, meter)
    except ImportError as e:  # reported, rc 1: chip_smoke.py alone
        print(f"FAIL: the repro package is not next to chip_smoke.py: {e}",
              flush=True)
        return 1
    except Exception:  # every phase failure ends here, reported, rc 1
        traceback.print_exc()
        print("FAIL", flush=True)
        return 1
    print(f"[done] {time.perf_counter() - t0:.1f}s, compile "
          f"{meter.seconds:.1f}s, persistent-cache hits {meter.hits}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
