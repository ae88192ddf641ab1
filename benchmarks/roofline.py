"""Roofline analysis from the dry-run artifacts (deliverable g).

Per (arch x shape) on the single-pod mesh:
  compute term    = HLO_dot_FLOPs / peak_FLOPs          (per device, s)
  memory term     = 2 * HLO_bytes / HBM_bw              (write + read)
  collective term = collective_bytes / link_bw
with HLO quantities from the while-trip-aware analyzer
(repro/launch/hlo_analysis.py; cost_analysis() counts scan bodies once and
is unusable directly).  Also reports MODEL_FLOPS (6*N_active*D for train,
2*N_active*tokens for serve) and the useful-compute ratio
MODEL_FLOPS / (devices * HLO_FLOPs), which exposes remat/redundancy waste.

  PYTHONPATH=src python -m benchmarks.roofline [--dir results/dryrun]
      [--multi-pod] [--write results/roofline.json]

``--nm-shard`` prints the shard-local analysis of the K-sharded 2:4 kernel
(kernels/shard.py): per-device arithmetic intensity, bytes moved, and the
explicit psum payload against the ICI link bandwidth - the decision surface
for when K-partial accumulation beats a replicated kernel.
"""
from __future__ import annotations

import argparse
import json
import pathlib

import jax
import jax.numpy as jnp

from repro.configs.base import ARCH_IDS, SHAPE_CELLS, get_config

# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" system architecture:
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect per chip over its 4 ICI links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9, "ici_link_bytes_s": 1600e9 / 8 / 4},
}
# the chip the production dry run lowers for (launch/mesh.py)
DRYRUN_KIND = "TPU v5 lite"


def peaks(kind: str = DRYRUN_KIND) -> dict:
    """Peak table row for a device kind; a kind with no row is an error,
    never a silent default."""
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {kind!r}: add "
                       "a PEAKS row with its source")
    return PEAKS[kind]


def _param_counts(arch: str) -> tuple[float, float]:
    """(N_total, N_active) from the real config's param shapes."""
    from repro.models import model as M
    cfg = get_config(arch)
    shapes = M.param_shapes(cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    total = active = 0.0
    for kp, s in flat:
        n = 1
        for d in s.shape:
            n *= d
        path = jax.tree_util.keystr(kp)
        total += n
        if "['moe']" in path and len(s.shape) == 4 and "shared" not in path:
            # stacked expert kernels (L, E, d, f): only top_k/E active
            active += n * cfg.top_k / max(cfg.num_experts, 1)
        else:
            active += n
    return total, active


def model_flops(arch: str, cell_name: str) -> float:
    cell = SHAPE_CELLS[cell_name]
    n_total, n_active = _param_counts(arch)
    if cell.kind == "train":
        return 6.0 * n_active * cell.global_batch * cell.seq_len
    if cell.kind == "prefill":
        return 2.0 * n_active * cell.global_batch * cell.seq_len
    return 2.0 * n_active * cell.global_batch  # one decoded token


def analyze_cell(dirpath: pathlib.Path, arch: str, cell: str,
                 multi_pod: bool) -> dict | None:
    tag = f"{arch}__{cell}__{'multipod' if multi_pod else 'pod'}"
    jf = dirpath / f"{tag}.json"
    if not jf.exists():
        return None
    rec = json.loads(jf.read_text())
    if rec.get("skipped"):
        return {"arch": arch, "cell": cell, "skipped": rec["skipped"]}
    if rec.get("error"):
        return {"arch": arch, "cell": cell, "error": rec["error"]}
    from repro.launch.hlo_analysis import analyze_file
    s = analyze_file(dirpath / f"{tag}.hlo.gz")
    n_dev = rec["devices"]
    pk = peaks()
    t_c = s.dot_flops / pk["flops_bf16"]
    t_m = 2.0 * s.bytes_out / pk["hbm_bytes_s"]
    t_x = s.coll_bytes / pk["ici_link_bytes_s"]
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))
    mf = model_flops(arch, cell)
    ratio = mf / max(n_dev * s.dot_flops, 1e-30)
    return {
        "arch": arch, "cell": cell, "devices": n_dev,
        "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
        "dominant": dom[1],
        "hlo_flops_per_dev": s.dot_flops,
        "hlo_bytes_per_dev": s.bytes_out,
        "coll_bytes_per_dev": s.coll_bytes,
        "coll_by_op": s.coll_by_op,
        "model_flops_global": mf,
        "useful_ratio": ratio,
        "hbm_per_dev_gb": rec.get("per_device_hbm_bytes", 0) / 1e9,
        "fits_16gb": rec.get("fits_16gb"),
        "compile_s": rec.get("compile_s"),
        "roofline_fraction": t_c / max(t_c, t_m, t_x),
        "note": _note(dom[1], ratio, s),
    }


def _note(dom: str, ratio: float, s) -> str:
    if dom == "compute":
        if ratio < 0.5:
            return ("compute-bound but only {:.0%} useful - cut remat "
                    "recompute or redundant (replicated) matmuls".format(ratio))
        return "compute-bound; gains need better MXU shapes or less remat"
    if dom == "memory":
        return ("memory-bound; fuse elementwise chains / shrink saved "
                "activations (bytes dominate flops)")
    ag = s.coll_by_op.get("all-gather", 0)
    ar = s.coll_by_op.get("all-reduce", 0)
    which = "all-gather (FSDP weight gathers)" if ag >= ar else \
        "all-reduce (grad sync)"
    return f"collective-bound, dominated by {which}; overlap or re-shard"


def nm_shard_roofline(M: int, K: int, N: int, *, devices: int = 1,
                      idx_bits: int = 2, act_bytes: int = 2) -> dict:
    """Shard-local roofline of one K-sharded 2:4 kernel call.

    Each device holds a (K/d, N) slice of the compressed kernel - vals
    (K/(2d), N) bf16 plus the index plane (K/(8d), N) packed-2-bit or
    (K/(2d), N) int8 - streams its x slice (M, K/d), and produces an f32
    partial (M, N) that ONE psum over the K axis combines (payload
    M*N*4 bytes per device, counted by the ``dist.psum_bytes`` site
    counters at trace time).  FLOPs count the kept weights only
    (2 * M * K/2 * N multiply-adds, split d ways); a replicated kernel is
    the devices=1 row with zero collective time.
    """
    k_loc = K / devices
    flops = 2.0 * M * (K / 2) * N / devices        # kept-weight MACs
    vals_b = (k_loc / 2) * N * 2                   # bf16 vals slice
    idx_b = (k_loc / 8) * N if idx_bits == 2 else (k_loc / 2) * N
    x_b = M * k_loc * act_bytes
    out_b = M * N * 4                              # f32 partial write
    bytes_moved = vals_b + idx_b + x_b + out_b
    psum_b = 0.0 if devices == 1 else M * N * 4    # per-device psum payload
    pk = peaks()
    t_c = flops / pk["flops_bf16"]
    t_m = bytes_moved / pk["hbm_bytes_s"]
    t_x = psum_b / pk["ici_link_bytes_s"]
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))
    return {
        "M": M, "K": K, "N": N, "devices": devices, "idx_bits": idx_bits,
        "flops_per_dev": flops, "bytes_per_dev": bytes_moved,
        "arith_intensity": flops / bytes_moved,
        "psum_bytes_per_dev": psum_b,
        "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
        "t_total_s": max(t_c, t_m) + t_x, "dominant": dom[1],
    }


def nm_shard_table(arch: str = "llama3.2-1b", M: int = 8,
                   device_counts=(1, 4, 8)) -> list[dict]:
    """K-sharded kernel roofline over one decode step's projection shapes.

    Decode is tiny-M (M = batch of slots), so the compressed weight bytes
    dominate ``bytes_per_dev`` and K-sharding divides exactly the dominant
    term while the psum payload (M*N*4) stays M-small - the table shows the
    memory-time win per device count next to the collective time it buys.
    """
    cfg = get_config(arch)
    h = cfg.num_heads * cfg.head_dim
    kv = cfg.num_kv_heads * cfg.head_dim
    shapes = [("wq", cfg.d_model, h), ("wk", cfg.d_model, kv),
              ("wv", cfg.d_model, kv), ("wo", h, cfg.d_model),
              ("up+gate", cfg.d_model, 2 * cfg.d_ff),
              ("down", cfg.d_ff, cfg.d_model)]
    rows = []
    for name, K, N in shapes:
        for d in device_counts:
            r = nm_shard_roofline(M, K, N, devices=d)
            r["proj"] = name
            rows.append(r)
    return rows


def _print_nm_shard(M: int) -> None:
    rows = nm_shard_table(M=M)
    print(f"K-sharded 2:4 kernel, shard-local roofline (decode M={M}):")
    print(f"{'proj':10s} {'KxN':>12s} {'dev':>4s} {'AI':>7s} "
          f"{'MB/dev':>8s} {'psum KB':>8s} {'t_mem':>9s} {'t_coll':>9s} "
          f"{'dom':>6s}")
    for r in rows:
        print(f"{r['proj']:10s} {r['K']:>5d}x{r['N']:<6d} "
              f"{r['devices']:>4d} {r['arith_intensity']:7.2f} "
              f"{r['bytes_per_dev'] / 1e6:8.3f} "
              f"{r['psum_bytes_per_dev'] / 1e3:8.2f} "
              f"{r['t_memory_s']:9.2e} {r['t_collective_s']:9.2e} "
              f"{r['dominant'][:6]:>6s}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--write", default="results/roofline.json")
    ap.add_argument("--nm-shard", action="store_true",
                    help="shard-local roofline of the K-sharded 2:4 kernel")
    ap.add_argument("--decode-batch", type=int, default=8,
                    help="decode batch M for --nm-shard")
    args = ap.parse_args()
    if args.nm_shard:
        _print_nm_shard(args.decode_batch)
        if args.write:
            p = pathlib.Path(args.write)
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text(json.dumps(nm_shard_table(M=args.decode_batch),
                                    indent=1))
            print("wrote", args.write)
        return
    d = pathlib.Path(args.dir)
    rows = []
    for arch in ARCH_IDS:
        for cell in SHAPE_CELLS:
            r = analyze_cell(d, arch, cell, args.multi_pod)
            if r is not None:
                rows.append(r)
    hdr = (f"{'arch':22s} {'cell':12s} {'t_comp':>9s} {'t_mem':>9s} "
           f"{'t_coll':>9s} {'dom':>6s} {'useful':>7s} {'HBM GB':>7s}")
    print(hdr)
    for r in rows:
        if r.get("skipped"):
            print(f"{r['arch']:22s} {r['cell']:12s} SKIP ({r['skipped'][:48]})")
            continue
        if r.get("error"):
            print(f"{r['arch']:22s} {r['cell']:12s} ERROR")
            continue
        print(f"{r['arch']:22s} {r['cell']:12s} "
              f"{r['t_compute_s']:9.2e} {r['t_memory_s']:9.2e} "
              f"{r['t_collective_s']:9.2e} {r['dominant'][:6]:>6s} "
              f"{r['useful_ratio']:7.2f} {r['hbm_per_dev_gb']:7.2f}")
    if args.write:
        pathlib.Path(args.write).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.write).write_text(json.dumps(rows, indent=1))
        print("wrote", args.write)


if __name__ == "__main__":
    main()
