"""Abstract pipeline auditor: memory planner, sharding checker, zoo dry-run.

The acceptance criteria live here: the static liveness walk agrees with
compiled ``memory_analysis()`` within 10% on the llama + mixtral smoke
configs, the static SearchState estimate equals the live figure
``results/bench/BENCH_calibrate.json`` records, and the whole-zoo dry-run
matches its committed golden contracts.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).parent.parent


# ---------------------------------------------------------------------------
# memplan: SearchState static bytes == live bench figure
# ---------------------------------------------------------------------------

def test_search_state_bytes_matches_live_bench():
    """eval_shape of init_search must reproduce the byte count the live
    calibration benchmark measured off real buffers - the planner's fit
    table is only trustworthy if the static and live layouts agree."""
    from repro.analysis import memplan
    static = memplan.search_state_bytes("llama3.2-1b")
    bench = json.loads((REPO / "results/bench/BENCH_calibrate.json")
                       .read_text())
    assert bench["arch"] == "llama3.2-1b" and bench.get("smoke", True)
    assert static == bench["search_state_bytes"] == 7344652


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mixtral-8x22b"])
def test_memplan_within_10pct_of_compiled(arch):
    """Acceptance criterion: static peak bytes within 10% of compiled
    ``memory_analysis()`` on the dense decode surface of both smoke
    configs.  (bf16 surfaces diverge on the CPU backend only because XLA
    stages f32 copies of bf16 GEMM operands - memplan reports that
    separately as ``bf16_staging_bytes``.)"""
    from repro.analysis import memplan, surfaces
    surf = surfaces.serve_surfaces(arch, mesh_shape=None, sparse=False)[0]
    assert surf.name == "decode"
    res = memplan.crosscheck(surf.fn, *surf.args, surface=surf.name)
    assert res["compiled"]["total_bytes"] > 0
    assert abs(res["rel_err"]) <= 0.10, res


def test_memplan_extracts_pallas_vmem_blocks():
    """BlockSpec-derived VMEM footprints for every pallas_call in the
    sparse decode jaxpr: nonzero bytes, plausible bound (v5e VMEM 128MB)."""
    import jax
    from repro.analysis import memplan, surfaces
    surf = surfaces.serve_surfaces("llama3.2-1b", mesh_shape=None)[0]
    closed = jax.make_jaxpr(surf.fn)(*surf.args)
    plan = memplan.plan_jaxpr(closed, surface="decode")
    assert plan.pallas, "sparse decode must run through pallas kernels"
    for pc in plan.pallas:
        assert pc.vmem_bytes > 0 and pc.vmem_bytes < 128 * 2**20, pc
        assert pc.n_blocks > 0
    names = {pc.name for pc in plan.pallas}
    assert any("nm" in n or "matmul" in n for n in names), names


def test_search_plan_streaming_threshold():
    """The O(sqrt N) table: a generous budget makes streaming optional
    (g_max == L); shrinking the budget below W + shadows forces a smaller
    group; below W + shadows/L even g=1 overflows (g_max None)."""
    from repro.analysis import memplan
    gen = memplan.search_plan("llama3.2-1b", smoke=True,
                              device_counts=(1,), budget_gb=16.0)
    L = gen["num_layers"]
    row = gen["per_mesh"][0]
    assert row["fits"] and row["max_group_layers"] == L
    assert not row["streaming_mandatory"]
    assert 1 <= gen["sqrt_group_layers"] <= L

    w, sh = gen["w_bytes"], gen["shadow_bytes"]
    mid = (w + sh / L * (L / 2)) / 1e9          # fits ~L/2 groups only
    tight = memplan.search_plan("llama3.2-1b", smoke=True,
                                device_counts=(1,), budget_gb=mid)
    t = tight["per_mesh"][0]
    assert t["streaming_mandatory"] and 1 <= t["max_group_layers"] < L

    none = memplan.search_plan("llama3.2-1b", smoke=True,
                               device_counts=(1,),
                               budget_gb=(w * 0.5) / 1e9)
    assert none["per_mesh"][0]["max_group_layers"] is None


# ---------------------------------------------------------------------------
# zoo: family reports + golden contracts
# ---------------------------------------------------------------------------

def test_zoo_llama_matches_committed_golden_1dev():
    """One family end-to-end against its committed golden (the full-zoo
    sweep runs in CI); drift in any pinned fact fails structurally."""
    from repro.analysis import zoo
    man = zoo.build_zoo_manifest("llama3.2-1b", mesh_shape=None)
    golden = json.loads(
        (REPO / "results/contracts/zoo/llama3.2-1b_1dev.json").read_text())
    assert zoo.zoo_diff(golden, man) == []
    assert man["feasibility"]["traces"] and man["feasibility"]["fits_16gb"]
    st = man["stages"]
    assert st["calibrate"]["search_state_bytes"] == 7344652
    assert st["engine_decode"]["host_callbacks"] == 0
    assert st["sparsify"]["kernel_native_packed"] == 7
    assert st["fleet"]["shared_leaves"] == 4


def test_zoo_whisper_structured_skip():
    """Encoder-decoder families cannot use the slot engine; the zoo must
    emit a structured skip AND still audit decode_step directly."""
    from repro.analysis import zoo
    man = zoo.build_zoo_manifest("whisper-small", mesh_shape=None)
    ed = man["stages"]["engine_decode"]
    assert ed["status"] == "skip" and "encoder-decoder" in ed["reason"]
    assert ed["surface"] == "decode_step" and ed["host_callbacks"] == 0
    assert man["feasibility"]["traces"]


def test_zoo_xlstm_nm_infeasible_skip():
    """xlstm's ff_down kernel (K=85) breaks 2:4 grouping: the sparsify
    stage skips with the offending leaf named, the bank re-thresholds
    unstructured budgets instead, and serving audits masked-dense."""
    from repro.analysis import zoo
    man = zoo.build_zoo_manifest("xlstm-125m", mesh_shape=None)
    sp = man["stages"]["sparsify"]
    assert sp["status"] == "skip" and "K=85" in sp["reason"]
    assert man["stages"]["bank"]["budgets"] == 2
    assert man["stages"]["engine_decode"]["sparse"] is False
    assert man["feasibility"]["traces"]


def test_zoo_diff_ignores_info_flags_drift(tmp_path):
    from repro.analysis import zoo
    golden = {"family": "x", "stages": {"bank": {"budgets": 2}},
              "info": {"jax": "0.0.0"}}
    same = {"family": "x", "stages": {"bank": {"budgets": 2}},
            "info": {"jax": "9.9.9"}}
    assert zoo.zoo_diff(golden, same) == []
    drift = {"family": "x", "stages": {"bank": {"budgets": 3}},
             "info": {"jax": "9.9.9"}}
    diffs = zoo.zoo_diff(golden, drift)
    assert len(diffs) == 1 and diffs[0]["path"].endswith("bank.budgets")
    missing = {"family": "x", "stages": {}, "info": {}}
    assert any(d["current"] == "<missing>"
               for d in zoo.zoo_diff(golden, missing))


def test_zoo_run_update_then_check_roundtrip(tmp_path):
    """run_zoo --update writes a golden that the very next check accepts;
    a missing golden fails with a structured diff artifact."""
    from repro.analysis import zoo
    d = tmp_path / "zoo"
    assert zoo.run_zoo(["llama3.2-1b"], zoo_dir=d, update=True) == 0
    assert zoo.run_zoo(["llama3.2-1b"], zoo_dir=d) == 0
    diff_out = tmp_path / "diff.json"
    rc = zoo.run_zoo(["gemma3-1b"], zoo_dir=d, diff_out=diff_out)
    assert rc == 1 and json.loads(diff_out.read_text())


# ---------------------------------------------------------------------------
# shardcheck (mesh runs in a forced-4-device subprocess, as test_tp does)
# ---------------------------------------------------------------------------

def _run_forced_4dev(code: str) -> None:
    prelude = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=4")
        os.environ["JAX_PLATFORMS"] = "cpu"
    """)
    env = {**os.environ, "PYTHONPATH": "src"}
    r = subprocess.run([sys.executable, "-c",
                        prelude + textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       cwd=str(REPO), timeout=1200)
    assert r.returncode == 0 and "ok" in r.stdout, (r.stdout, r.stderr)


def test_shardcheck_1dev_is_structured_skip():
    from repro.analysis import shardcheck
    rep = shardcheck.check_arch("llama3.2-1b", mesh_shape=None)
    assert rep["clean"] and rep["skipped"] and rep["findings"] == []


def test_shardcheck_leaves_and_psums_clean_4dev():
    """On the (2,2) mesh every llama compressed leaf K-shards (no silent
    replicated fallback), every decode psum axis is partitioned in an
    input and absent from the outputs, and a deliberately unpartitioned
    psum IS flagged (the checker can fail, not just pass)."""
    _run_forced_4dev("""
    import jax
    from repro.launch.mesh import make_mesh
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.analysis import shardcheck

    rep = shardcheck.check_arch("llama3.2-1b", mesh_shape=(2, 2))
    assert rep["clean"], rep["findings"]
    lv = rep["leaves"]
    assert lv["sparse_leaves"] == lv["k_sharded"] == 7, lv
    assert lv["replicated_k"] == 0 and rep["surfaces"]["decode"]["psums"] > 0

    # negative control: psum over an axis no input spec partitions (with
    # check_vma off, as in kernels/shard.py: with it on, jax folds a psum
    # of an axis-invariant value into a multiply and no psum is left)
    mesh = make_mesh((2, 2), ("data", "model"))
    bad = jax.shard_map(lambda x: jax.lax.psum(x, "model"), mesh=mesh,
                        in_specs=(P("data"),), out_specs=P("data"),
                        check_vma=False)
    closed = jax.make_jaxpr(bad)(jnp.ones((4, 8)))
    counts, findings = shardcheck.check_psum_axes(closed, surface="bad")
    assert counts["psums"] == 1
    assert any(f["kind"] == "psum_axis_unpartitioned" for f in findings)

    # xlstm auto-falls back to the dense engine and stays clean
    rx = shardcheck.check_arch("xlstm-125m", mesh_shape=(2, 2))
    assert rx["clean"] and rx["leaves"]["sparse_leaves"] == 0
    assert "2:4 infeasible" in rx["sparse_note"]
    print("ok")
    """)


def test_zoo_golden_matches_4dev_mesh():
    """The CI mesh variant: llama's 2x2 zoo golden reproduces under 4
    forced devices, with the shardcheck stage clean."""
    _run_forced_4dev("""
    import json
    from repro.analysis import zoo
    man = zoo.build_zoo_manifest("llama3.2-1b", mesh_shape=(2, 2))
    golden = json.loads(
        open("results/contracts/zoo/llama3.2-1b_2x2.json").read())
    assert zoo.zoo_diff(golden, man) == []
    sc = man["stages"]["shardcheck"]
    assert sc["status"] == "ok" and sc["clean"]
    assert man["feasibility"]["sharding_clean"] is True
    print("ok")
    """)
