"""Mask-bank round trip: calibrate ONCE, serve at FOUR budgets (paper §4.3
+ Table 8 scenario).

Run 1 is the ``repro.launch.calibrate`` entry point: jitted sharded stats
-> scanned mirror-descent search -> mask-bank artifact
(Gamma/V/stats/PruneConfig).  Runs 2-4 never touch calibration again: they
load the bank, re-threshold to masks in one shot, and serve - first with
2:4-compressed weights executing through the nm_spmm kernel, then
masked-dense for an A/B token check, then a sparsity FLEET serving dense +
unstructured + 2:4 concurrently behind one router with weighted A/B
traffic.

  PYTHONPATH=src python examples/serve_sparse.py --arch llama3.2-1b
  PYTHONPATH=src python examples/serve_sparse.py --arch gemma2-2b \
      --sparsity 0.6 --gen 32

Every run is its own process, and this parent never imports JAX: on a TPU
host the chip belongs to one process at a time, so each child can take it
in turn.
"""
import argparse
import subprocess
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--sparsity", type=float, default=None,
                    help="unstructured re-threshold budget (default: the "
                         "calibrated 2:4 pattern)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--artifact", default=None,
                    help="bank directory (default results/bank/<arch>)")
    args = ap.parse_args()
    artifact = args.artifact or f"results/bank/{args.arch}"

    base = [sys.executable, "-m", "repro.launch.serve", "--arch", args.arch,
            "--smoke", "--batch", "4", "--prompt-len", "64",
            "--gen", str(args.gen)]
    sparsity = (["--sparsity", str(args.sparsity)]
                if args.sparsity is not None else [])

    runs = [
        # 1: calibrate once (the single entry point), persist the bank
        [sys.executable, "-m", "repro.launch.calibrate", "--arch", args.arch,
         "--smoke", "--out", artifact, "--metric", "wanda", "--mode", "nm",
         "--steps", "30", "--seq", "64"],
        # 2: serve compressed from the bank - no re-calibration
        base + ["--sparse-artifact", artifact] + sparsity,
        # 3: same masks, masked-dense weights - tokens must match run 2
        base + ["--sparse-artifact", artifact, "--weight-format", "masked"]
        + sparsity,
        # 4: the same ONE bank serving three budgets concurrently, A/B split
        base + ["--sparse-artifact", artifact, "--fleet", "0.0,0.5,2:4",
                "--ab", "1,1,2"],
    ]
    for cmd in runs:
        print("+", " ".join(cmd), flush=True)
        rc = subprocess.call(cmd)
        if rc:
            raise SystemExit(rc)


if __name__ == "__main__":
    main()
