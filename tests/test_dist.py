"""Sharding-rule derivation (no multi-device needed: pure spec logic)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config, SHAPE_CELLS
from repro.dist import sharding as shd
from repro.dist.axes import ShardingRules, make_rules
from repro.launch.mesh import make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def test_spec_dedupes_repeated_mesh_axes(mesh):
    rules = ShardingRules(mesh=mesh, rules={"a": "model", "b": "model"})
    spec = rules.spec(["a", "b"])
    assert spec == P("model", None)


def test_params_sharding_divisibility_fallback(mesh):
    rules = make_rules(mesh)
    # 3 not divisible by model axis of a >1 mesh; with size-1 axes all pass,
    # so emulate via a fake shape check on the spec helper
    axes = {"k": "embed|mlp"}
    shapes = {"k": jax.ShapeDtypeStruct((6, 4), jnp.float32)}
    out = shd.params_sharding(axes, shapes, rules)
    assert out["k"].spec == P("data", "model")


def test_make_rules_seq_parallel_toggle(mesh):
    r1 = make_rules(mesh, seq_parallel=False)
    r2 = make_rules(mesh, seq_parallel=True)
    assert r1.rules["act_seq"] is None
    assert r2.rules["act_seq"] == "model"


def test_cache_sharding_layouts(mesh):
    cs = {
        "0": {"k": jax.ShapeDtypeStruct((4, 8, 4096, 2, 64), jnp.bfloat16),
              "v": jax.ShapeDtypeStruct((4, 8, 4096, 2, 64), jnp.bfloat16)},
    }
    out = shd.cache_sharding(cs, mesh)
    spec = out["0"]["k"].spec
    assert spec[0] is None              # layers axis never sharded
    assert spec[1] in ("data", ("data",))  # batch over dp
    assert spec[2] == "model"           # capacity TP (partial softmax)
    # long-context batch=1 -> seq sharded over every divisible axis
    cs2 = {"0": {"k": jax.ShapeDtypeStruct((4, 1, 8192, 2, 64),
                                           jnp.bfloat16)}}
    out2 = shd.cache_sharding(cs2, mesh)
    assert out2["0"]["k"].spec[2] is not None


@pytest.fixture(scope="module")
def mesh22():
    """2x2 multi-device mesh (abstract: spec derivation is pure logic, the
    divisibility checks see real axis sizes > 1)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh((2, 2), ("data", "model"))


def test_params_sharding_sparse_leaves_2d_mesh(mesh22):
    """SparseTensor components inherit the dense kernel's (K, N) axes:
    vals/idx take the N sharding; the K sharding survives the halved (vals)
    and packed-eighthed (idx) dims exactly when they still divide."""
    from repro.kernels import ref as kref
    from repro.sparse import pack
    rules = make_rules(mesh22)
    w = jax.random.normal(jax.random.key(0), (64, 64), jnp.float32)
    st = pack.pack_nm(w, kref.nm_mask_ref(w), idx_bits=2)
    out = shd.params_sharding({"kernel": "embed|mlp"}, {"kernel": st}, rules)
    sh = out["kernel"]
    assert sh.vals.spec == P("data", "model")   # (32, 64): K/2 divides dp=2
    assert sh.idx.spec == P("data", "model")    # (8, 64): K/8 divides dp=2
    assert sh.idx_bits == 2                     # tree node mirrors the leaf


def test_params_sharding_sparse_idx_divisibility_fallback(mesh22):
    """K = 8 cannot K-shard over data=2 for a packed2 plane (needs K % 16
    == 0): BOTH planes replicate along K (all-or-nothing - a vals-only K
    shard could never feed the shard-local kernel) and a structured warning
    names the leaf; the N sharding survives."""
    from repro.kernels import ref as kref
    from repro.sparse import pack
    rules = make_rules(mesh22)
    w = jax.random.normal(jax.random.key(1), (8, 64), jnp.float32)
    st = pack.pack_nm(w, kref.nm_mask_ref(w), idx_bits=2)
    with pytest.warns(UserWarning, match="cannot shard over mesh axis"):
        out = shd.params_sharding({"kernel": "embed|mlp"}, {"kernel": st},
                                  rules)
    assert out["kernel"].vals.spec == P(None, "model")
    assert out["kernel"].idx.spec == P(None, "model")


def test_params_sharding_stacked_sparse_and_bitmask(mesh22):
    """Scan-stacked compressed leaves keep the unsharded layers axis;
    BitMask buffers (flat bytes, no meaningful axis) replicate."""
    from repro.kernels import ref as kref
    from repro.sparse import pack
    from repro.sparse.formats import BitMask
    rules = make_rules(mesh22)
    w = jax.random.normal(jax.random.key(2), (3, 64, 64), jnp.float32)
    mask = jnp.stack([kref.nm_mask_ref(w[i]) for i in range(3)])
    st = pack.pack_nm(w, mask, idx_bits=2)
    bm = BitMask.pack(mask[0])
    out = shd.params_sharding({"kernel": "layers|embed|mlp", "mask": None},
                              {"kernel": st, "mask": bm}, rules)
    assert out["kernel"].vals.spec == P(None, "data", "model")
    assert out["kernel"].idx.spec == P(None, "data", "model")
    assert out["mask"].bits.spec == P()


def test_params_sharding_expert_bank_leaves(mesh22):
    """Expert-banked compressed leaves (layers, E, K, N): the leading expert
    axis maps to the "experts" logical axis (-> "model"), and the (K, N)
    component rules apply per expert - vals K/2 and idx K/8 keep their
    sharding when they still divide, with the usual fallback."""
    from repro.kernels import ref as kref
    from repro.sparse import pack
    rules = make_rules(mesh22)
    w = jax.random.normal(jax.random.key(3), (2, 2, 32, 64), jnp.float32)
    mask = jnp.stack([jnp.stack([kref.nm_mask_ref(w[l, e])
                                 for e in range(2)]) for l in range(2)])
    st = pack.pack_nm(w, mask, idx_bits=2)
    # expert-parallel bank (deepseek-style): experts -> model, so the
    # per-expert N dim ("mlp" -> model too) falls back to replicated
    out = shd.params_sharding({"kernel": "layers|experts|embed|mlp"},
                              {"kernel": st}, rules)
    assert out["kernel"].vals.spec == P(None, "model", "data", None)
    assert out["kernel"].idx.spec == P(None, "model", "data", None)
    # tensor-parallel bank (mixtral-style, expert axis unsharded): the
    # trailing dims keep the plain (K, N) component rules per expert
    out2 = shd.params_sharding({"kernel": "layers||embed|mlp"},
                               {"kernel": st}, rules)
    assert out2["kernel"].vals.spec == P(None, None, "data", "model")
    assert out2["kernel"].idx.spec == P(None, None, "data", "model")


def test_sparse_leaf_device_put_multidevice():
    """End-to-end placement on a real 2x2 mesh (forced host devices in a
    subprocess: XLA device count is fixed at jax import): the compressed
    tree device_puts with the derived shardings, every component lands
    sharded, and the sharded tensor still decompresses exactly."""
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.dist import sharding as shd
        from repro.dist.axes import make_rules
        from repro.kernels import ref as kref
        from repro.sparse import pack
        mesh = make_mesh((2, 2), ("data", "model"))
        rules = make_rules(mesh)
        w = jax.random.normal(jax.random.key(0), (64, 64), jnp.float32)
        st = pack.pack_nm(w, kref.nm_mask_ref(w), idx_bits=2)
        dense0 = np.asarray(st.to_dense())
        tree = {"kernel": st}
        sh = shd.params_sharding({"kernel": "embed|mlp"}, tree, rules)
        placed = jax.device_put(tree, sh)
        pst = placed["kernel"]
        assert len(pst.vals.addressable_shards) == 4
        assert pst.vals.addressable_shards[0].data.shape == (16, 32)
        assert pst.idx.addressable_shards[0].data.shape == (4, 32)
        np.testing.assert_array_equal(np.asarray(pst.to_dense()), dense0)
        print("ok")
    """)
    env = {**__import__("os").environ, "PYTHONPATH": "src"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=str(
                           __import__("pathlib").Path(__file__).parent.parent))
    assert r.returncode == 0 and "ok" in r.stdout, (r.stdout, r.stderr)



def test_init_params_and_search_state_built_sharded_multidevice():
    """On a real 2x2 mesh (forced host devices in a subprocess) the params
    and the mirror-descent state are created already sharded - device 0
    holds only its shards of W, Gamma and V - and the sharded init equals
    the unsharded one value for value."""
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import dataclasses
        import jax, numpy as np
        from repro.configs.base import PruneConfig, get_smoke_config
        from repro.core import calibrate as cal
        from repro.data.synthetic import batches_for
        from repro.dist import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models import model as M
        cfg = get_smoke_config("llama3.2-1b")
        rules = shd.make_production_rules(make_mesh((2, 2), ("data",
                                                             "model")))
        p = shd.init_params_sharded(cfg, jax.random.key(0), rules)
        ref = M.init_params(cfg, jax.random.key(0))
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        want = shd.params_sharding(M.param_axes(cfg), M.param_shapes(cfg),
                                   rules)
        for a, sh in zip(jax.tree.leaves(p), jax.tree.leaves(want)):
            assert a.sharding.is_equivalent_to(sh, a.ndim), (a.sharding, sh)
        assert any(not a.sharding.is_fully_replicated
                   for a in jax.tree.leaves(p))
        pcfg = PruneConfig(local_metric="wanda", mode="nm", steps=1,
                           scan_chunk=1)
        calib = batches_for(cfg, n=1, batch=4, seq=16, split="calib")
        stats = cal.collect_stats(cfg, p, calib, rules=rules)
        state, _ = cal.run_search(cfg, pcfg, p, calib, stats, rules=rules)
        for tree in (state.W, state.Gamma, state.V):
            assert any(a.addressable_shards[0].data.size < a.size
                       for a in jax.tree.leaves(tree))
        print("ok")
    """)
    env = {**__import__("os").environ, "PYTHONPATH": "src"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=str(
                           __import__("pathlib").Path(__file__).parent.parent))
    assert r.returncode == 0 and "ok" in r.stdout, (r.stdout, r.stderr)

def test_all_full_configs_have_valid_stages():
    from repro.models import model as M
    for arch in ["yi-6b", "mixtral-8x22b", "zamba2-7b", "gemma3-1b",
                 "deepseek-v2-lite-16b"]:
        cfg = get_config(arch)
        total = sum(len(p) * r for p, r in M.make_stages(cfg))
        assert total == cfg.num_layers


def test_param_axes_structure_matches_params():
    from repro.configs.base import get_smoke_config
    from repro.models import model as M
    cfg = get_smoke_config("llama3.2-1b")
    shapes = M.param_shapes(cfg)
    axes = M.param_axes(cfg)
    sf = jax.tree_util.tree_structure(shapes)
    af = jax.tree_util.tree_structure(axes)
    assert sf == af
    for s, a in zip(jax.tree.leaves(shapes), jax.tree.leaves(axes)):
        assert len(a.split("|")) == len(s.shape), (a, s.shape)
