"""Tensor-parallel sparse serving bench: K-sharded decode on a 4-device mesh.

XLA fixes the host device count at jax import, so the measurement runs in a
CHILD process launched with ``XLA_FLAGS=--xla_force_host_platform_device_count
=4`` - the parent (this module, imported by ``benchmarks/run.py`` after jax
is already up) parses the child's JSON and writes
``results/bench/BENCH_tp.json``.

Per mesh ((1, 4) pure TP and (2, 2) data x model), the child serves the
llama-smoke 2:4 engine sharded and replicated and reports:

* per-device tok/s (sharded) next to the replicated oracle's tok/s,
* the *static* collective count per decode trace, read from the
  ``dist.psum`` counters (they advance at trace time, so the delta around
  the first decode call IS the per-step count; a second same-shape decode
  must add zero - ``collectives_static``),
* ``tokens_match_replicated``: token-for-token parity vs the oracle.

Gated by ``benchmarks/run.py --smoke``: parity must hold, counts must be
static, and the fused up/gate pair must cost ONE psum (mlp site = 2 per
trace on (2, 2): the pair + down; 3 would mean the deferral regressed).

The child always runs on the CPU (``JAX_PLATFORMS=cpu``, four forced host
devices), even on a machine with a TPU: the parent has already used JAX and
holds the chip, so a child could not reach it anyway.  Its numbers are
functional - reference GEMMs, with the psums run through the same shard_map
the TPU path compiles - so the collective *counts* and the parity flag are
the invariants and the tok/s columns measure the CPU, never a chip.  The
four-chip measurement of this path is ``chip_smoke.py --four-chips``.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap

from benchmarks.table8_inference import write_serve_json

_CHILD = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import json, time
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro import obs
    from repro.configs.base import get_smoke_config
    from repro.core import masks as masks_mod, metrics as metrics_mod
    from repro.core.prunable import prunable_map
    from repro.dist.axes import make_rules
    from repro.models import model as M
    from repro.serve.engine import ServeEngine
    from repro.sparse import apply as apply_mod

    SITES = ("mlp", "attn", "moe", "attn_kv")
    SLOTS, CAPACITY, GEN = 4, 64, 24

    cfg = get_smoke_config("llama3.2-1b")
    params = M.init_params(cfg, jax.random.key(0))
    pr = prunable_map(params)
    scores = metrics_mod.metric_tree(
        "magnitude", params, jax.tree.map(lambda _: None, pr), pr)
    masks = masks_mod.nm_masks(scores)
    sparse = apply_mod.sparsify_params(
        params, masks, axes=M.param_axes(cfg), idx_bits=2,
        dtype=jnp.bfloat16)
    prompts = [(np.arange(1, 9) * (i + 3)) % cfg.vocab_size
               for i in range(SLOTS)]

    def snap(name):
        return {s: obs.counter_value(name, site=s) for s in SITES}

    def measure(rules):
        obs.configure(enabled=True)
        eng = ServeEngine(cfg, sparse, slots=SLOTS, capacity=CAPACITY,
                          rules=rules)
        toks = jnp.zeros((SLOTS,), jnp.int32)
        pos = jnp.zeros((SLOTS,), jnp.int32)
        b_n, b_bytes = snap("dist.psum"), snap("dist.psum_bytes")
        out, caches = eng._decode(eng.params, toks, eng.caches, pos)
        jax.block_until_ready(out)
        a_n, a_bytes = snap("dist.psum"), snap("dist.psum_bytes")
        out, _ = eng._decode(eng.params, toks, caches, pos + 1)
        jax.block_until_ready(out)
        c_n = snap("dist.psum")
        rids = [eng.submit(p, GEN) for p in prompts]
        t0 = time.perf_counter()
        res = eng.run()
        dt = time.perf_counter() - t0
        n_tok = sum(len(res[r]) for r in rids)
        return {
            "tokens": [res[r] for r in rids],
            "tok_s": n_tok / dt,
            "decode_psums_per_trace": {s: a_n[s] - b_n[s] for s in SITES},
            "decode_psum_bytes_per_trace": {s: a_bytes[s] - b_bytes[s]
                                            for s in SITES},
            "collectives_static": c_n == a_n,
        }

    oracle = measure(None)
    n_dev = jax.device_count()
    meshes = {}
    for shape in [(1, 4), (2, 2)]:
        mesh = make_mesh(shape, ("data", "model"))
        r = measure(make_rules(mesh))
        r["tokens_match_replicated"] = r.pop("tokens") == oracle["tokens"]
        r["tok_s_per_device"] = r["tok_s"] / n_dev
        meshes["x".join(map(str, shape))] = r
    oracle.pop("tokens")
    print("BENCH_TP_JSON=" + json.dumps({
        "devices": n_dev, "platform": jax.devices()[0].platform,
        "arch": cfg.name, "slots": SLOTS,
        "capacity": CAPACITY, "gen_tokens": GEN,
        "replicated": oracle, "meshes": meshes}))
"""


def tp_bench(out_rows: list) -> dict:
    """Run the forced-4-device child and fold its JSON into the bench rows."""
    env = {**os.environ, "PYTHONPATH": "src"}
    env.pop("REPRO_FORCE_REPLICATED", None)
    root = pathlib.Path(__file__).resolve().parent.parent
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(_CHILD)],
                       capture_output=True, text=True, env=env,
                       cwd=str(root), timeout=1200)
    marker = "BENCH_TP_JSON="
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith(marker)), None)
    assert r.returncode == 0 and line is not None, (r.stdout, r.stderr)
    result = json.loads(line[len(marker):])
    result["parity"] = all(m["tokens_match_replicated"]
                           for m in result["meshes"].values())
    result["collectives_static"] = all(m["collectives_static"]
                                       for m in result["meshes"].values())
    print(f"tensor-parallel serve ({result['devices']} forced CPU host "
          f"devices, {result['arch']}; CPU timings, not a chip "
          "measurement):")
    print(f"  replicated: {result['replicated']['tok_s']:8.1f} tok/s")
    for name, m in result["meshes"].items():
        psums = m["decode_psums_per_trace"]
        print(f"  mesh {name}: {m['tok_s']:8.1f} tok/s "
              f"({m['tok_s_per_device']:.1f}/device), "
              f"psums/decode-trace {psums}, "
              f"parity={m['tokens_match_replicated']}")
    out_rows.append({"table": "tp", **result})
    return result


def run(out_rows: list) -> None:
    tp_bench(out_rows)


if __name__ == "__main__":
    rows: list = []
    res = tp_bench(rows)
    print("wrote", write_serve_json(res, name="BENCH_tp.json"))
