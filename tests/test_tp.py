"""Tensor-parallel sparse serving: K-shard tags, partial-softmax combine,
and token parity of the shard-mapped engine against the replicated oracle.

Multi-device cases run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (XLA fixes the host
device count at jax import); spec/tag logic and the flash-partial combine
algebra run in-process on one device.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.dist import sharding as shd
from repro.dist.axes import make_rules, use_rules
from repro.launch.mesh import make_mesh


def _run_forced_4dev(code: str) -> None:
    """Run ``code`` under 4 forced host devices; assert it prints 'ok'."""
    prelude = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        os.environ["JAX_PLATFORMS"] = "cpu"
    """)
    env = {**os.environ, "PYTHONPATH": "src"}
    r = subprocess.run([sys.executable, "-c",
                        prelude + textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       cwd=str(pathlib.Path(__file__).parent.parent),
                       timeout=1200)
    assert r.returncode == 0 and "ok" in r.stdout, (r.stdout, r.stderr)


# ---------------------------------------------------------------------------
# Tag derivation (pure spec logic, abstract meshes)
# ---------------------------------------------------------------------------

def _pack(key, shape, idx_bits=2):
    from repro.kernels import ref as kref
    from repro.sparse import pack
    w = jax.random.normal(jax.random.key(key), shape, jnp.float32)
    if len(shape) == 2:
        mask = kref.nm_mask_ref(w)
    else:
        mask = jnp.stack([kref.nm_mask_ref(w[i]) for i in range(shape[0])])
    return pack.pack_nm(w, mask, idx_bits=idx_bits)


def test_tag_compressed_stamps_site_and_k_axis():
    """A K-shardable leaf gets (site, *entries) with the K mesh axis at
    [-2]; the site comes from the leaf path.  A leaf whose K cannot shard
    but whose N does is tagged with a None K entry (it runs under a
    shard_map with no psum); an unshardable leaf keeps shard=None and
    passes through by identity (no spurious retrace)."""
    rules = make_rules(AbstractMesh((2, 2), ("data", "model")))
    good = _pack(0, (64, 64))           # K=64 % (8*2) == 0 on either axis
    bad = _pack(1, (8, 64))             # K=8: no K shard possible
    none = _pack(6, (8, 63))            # neither K nor N=63 can shard
    tree = {"mlp": {"down": {"kernel": good}},
            "attn": {"wo": {"kernel": bad}, "wq": {"kernel": none}}}
    axes = {"mlp": {"down": {"kernel": "mlp|embed"}},
            "attn": {"wo": {"kernel": "qkv|embed"},
                     "wq": {"kernel": "qkv|embed"}}}
    out = shd.tag_compressed(axes, tree, rules)
    tag = out["mlp"]["down"]["kernel"].shard
    assert tag == ("mlp", "model", "data")
    assert out["mlp"]["down"]["kernel"].k_shard == "model"
    assert out["mlp"]["down"]["kernel"].shard_site == "mlp"
    # K replicated, N sharded: tagged for the shard_map, no K axis
    assert out["attn"]["wo"]["kernel"].shard == ("attn", None, "data")
    assert out["attn"]["wo"]["kernel"].k_shard is None
    # no warning from the quiet pass, leaf untouched by identity
    assert out["attn"]["wq"]["kernel"] is none
    assert out["attn"]["wq"]["kernel"].shard is None


def test_tag_compressed_strips_scanned_layers_axis():
    """Scan-stacked leaves (layers, K, N): the tag covers the *executed*
    dims only - lax.scan slices the layers axis away before dispatch, so a
    layers entry in the tag would misalign every executed-dim lookup."""
    rules = make_rules(AbstractMesh((2, 2), ("data", "model")))
    st = _pack(2, (3, 64, 64))
    out = shd.tag_compressed({"kernel": "layers|embed|mlp"},
                             {"kernel": st}, rules)
    tag = out["kernel"].shard
    assert tag is not None and len(tag) == 3    # (site, k, n): no layers
    assert out["kernel"].k_shard == "data"      # embed -> data


def test_tag_survives_tree_flatten_and_device_put_roundtrip():
    """The tag is static pytree aux: flatten/unflatten preserves it, and
    params_sharding mirrors the input leaf's aux verbatim so a tagged tree
    device_puts against its own sharding tree (treedefs must match)."""
    rules = make_rules(AbstractMesh((2, 2), ("data", "model")))
    st = _pack(3, (64, 64))
    tagged = shd.tag_compressed({"kernel": "mlp|embed"}, {"kernel": st},
                                rules)["kernel"]
    leaves, treedef = jax.tree_util.tree_flatten(tagged)
    rt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rt.shard == tagged.shard
    sh = shd.sparse_leaf_sharding("mlp|embed", tagged, rules)
    assert (jax.tree_util.tree_structure(sh)
            == jax.tree_util.tree_structure(tagged))


def test_k_sharded_gates_on_rules_tag_and_env(monkeypatch):
    """Dispatch routes shard-mapped only when a tag is present AND rules
    are installed; REPRO_FORCE_REPLICATED kills the route everywhere."""
    from repro.kernels import shard as ksh
    st = _pack(4, (64, 64))
    tagged = st.with_shard(("mlp", "model", None))
    mesh = make_mesh((1, 1), ("data", "model"))
    assert not ksh.k_sharded(tagged)            # no rules installed
    with use_rules(make_rules(mesh)):
        assert ksh.k_sharded(tagged)
        assert not ksh.k_sharded(st)            # untagged leaf
        assert ksh.pair_k_sharded(tagged, tagged)
        other = st.with_shard(("mlp", "data", None))
        assert not ksh.pair_k_sharded(tagged, other)   # different K axes
        monkeypatch.setenv(ksh.FORCE_REPLICATED_ENV, "1")
        assert not ksh.k_sharded(tagged)


def test_divisibility_fallback_is_all_or_nothing_and_loud():
    """K % (group * devices) != 0: BOTH components replicate along K (a
    vals-only K shard feeds no kernel) and the structured warning names the
    leaf path; byte-padded packed planes (K % 8 != 0) never qualify."""
    rules = make_rules(AbstractMesh((1, 4), ("data", "model")))
    st = _pack(5, (72, 128))            # 72 % 8 == 0 but 72 % 32 != 0
    from jax.sharding import PartitionSpec as P
    with pytest.warns(UserWarning, match="cannot shard over mesh axis"):
        out = shd.params_sharding({"kernel": "mlp|embed"}, {"kernel": st},
                                  rules)
    assert out["kernel"].vals.spec == P(None, "data")   # K replicated
    assert out["kernel"].idx.spec == P(None, "data")
    tagged = shd.tag_compressed({"kernel": "mlp|embed"}, {"kernel": st},
                                rules)["kernel"]
    assert tagged.shard is None


# ---------------------------------------------------------------------------
# Flash-partial combine algebra (single device)
# ---------------------------------------------------------------------------

def test_flash_partial_shard_combine_matches_full_softmax():
    """Splitting the capacity into shards, running the partial oracle per
    shard, and combining with the pmax/psum recipe the shard_map uses
    (corr = exp(m - m_global), one rescaled (l, acc) sum) reproduces the
    full-capacity softmax - including a fully-masked shard, whose m=-1e30
    makes its correction exactly zero."""
    from repro.kernels.flash_decode import (flash_decode_partial_ref,
                                            flash_decode_ref)
    B, C, K, G, D = 2, 32, 2, 2, 8
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, (B, K, G, D), jnp.float32)
    k = jax.random.normal(kk, (B, C, K, D), jnp.float32)
    v = jax.random.normal(kv, (B, C, K, D), jnp.float32)
    bias = jnp.zeros((B, C), jnp.float32)
    # mask the whole last quarter: shard 3 becomes all-masked
    bias = bias.at[:, 24:].set(-1e30)
    want = flash_decode_ref(q, k, v, bias)

    parts = [flash_decode_partial_ref(q, k[:, s:s + 8], v[:, s:s + 8],
                                      bias[:, s:s + 8])
             for s in range(0, C, 8)]
    mg = parts[0][1]
    for _, m, _ in parts[1:]:
        mg = jnp.maximum(mg, m)
    l_tot = sum(l * jnp.exp(m - mg) for _, m, l in parts)
    acc_tot = sum(acc * jnp.exp(m - mg) for acc, m, _ in parts)
    got = acc_tot / jnp.maximum(l_tot, 1e-30)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_flash_partial_ref_all_masked_shard_contributes_zero():
    """An entirely-masked shard flushes finite garbage (p = exp(0) once m
    clamps at -1e30) - what protects the combine is the flushed m itself:
    against any shard holding one real slot, corr = exp(-1e30 - m_global)
    is exactly 0, so the garbage partial is annihilated, not psummed."""
    from repro.kernels.flash_decode import flash_decode_partial_ref
    q = jnp.ones((1, 1, 2, 4), jnp.float32)
    k = jnp.ones((1, 8, 1, 4), jnp.float32)
    v = jnp.ones((1, 8, 1, 4), jnp.float32)
    bias = jnp.full((1, 8), -1e30, jnp.float32)
    acc, m, l = flash_decode_partial_ref(q, k, v, bias)
    assert np.isfinite(np.asarray(acc)).all()
    np.testing.assert_allclose(np.asarray(m), -1e30)
    live_m = jnp.zeros_like(m)          # any shard with a real slot
    corr = jnp.exp(m - jnp.maximum(m, live_m))
    np.testing.assert_allclose(np.asarray(corr), 0.0)


def test_infer_layout_is_shard_local():
    """Layout inference works from local shapes alone: the vals/idx row
    ratio (4:1 packed, 1:1 int8) is invariant under K sharding."""
    from repro.kernels.nm_spmm import infer_layout
    assert infer_layout(64, (8, 64)) == infer_layout(16, (2, 64))
    assert infer_layout(64, (32, 64)) == infer_layout(16, (8, 64))


# ---------------------------------------------------------------------------
# End-to-end token parity on a forced 4-device host mesh (subprocess)
# ---------------------------------------------------------------------------

_SPARSE_SETUP = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.configs.base import get_smoke_config
    from repro.core import masks as masks_mod, metrics as metrics_mod
    from repro.core.prunable import prunable_map
    from repro.dist.axes import make_rules
    from repro.models import model as M
    from repro.serve.engine import ServeEngine
    from repro.sparse import apply as apply_mod

    def sparse_smoke(arch, cfg=None):
        cfg = cfg or get_smoke_config(arch)
        params = M.init_params(cfg, jax.random.key(0))
        pr = prunable_map(params)
        scores = metrics_mod.metric_tree(
            "magnitude", params, jax.tree.map(lambda _: None, pr), pr)
        masks = masks_mod.nm_masks(scores)
        sparse = apply_mod.sparsify_params(
            params, masks, axes=M.param_axes(cfg), idx_bits=2,
            dtype=jnp.bfloat16)
        return cfg, sparse

    def serve(cfg, sparse, rules, prompts, n=6, slots=2, capacity=32):
        eng = ServeEngine(cfg, sparse, slots=slots, capacity=capacity,
                          rules=rules)
        rids = [eng.submit(p, n) for p in prompts]
        out = eng.run()
        return [out[r] for r in rids]
"""


def test_tp_token_parity_llama_4dev():
    """K-sharded 2:4 llama-smoke engine decodes token-identically to the
    replicated oracle on (1, 4) (K over "model": wo + down shard) and
    (2, 2) ("data" K-shards qkv and the fused up/gate pair too) meshes;
    REPRO_FORCE_REPLICATED=1 under the same rules also holds parity."""
    _run_forced_4dev(_SPARSE_SETUP + """
    cfg, sparse = sparse_smoke("llama3.2-1b")
    prompts = [np.arange(1, 9) % cfg.vocab_size,
               (np.arange(3, 13) * 7) % cfg.vocab_size]
    want = serve(cfg, sparse, None, prompts)
    for shape in [(1, 4), (2, 2)]:
        mesh = make_mesh(shape, ("data", "model"))
        got = serve(cfg, sparse, make_rules(mesh), prompts)
        assert got == want, (shape, got, want)
    import os
    os.environ["REPRO_FORCE_REPLICATED"] = "1"
    mesh = make_mesh((1, 4), ("data", "model"))
    got = serve(cfg, sparse, make_rules(mesh), prompts)
    assert got == want, ("forced-replicated", got, want)
    print("ok")
    """)


def test_tp_psum_counters_static_per_decode_trace():
    """The collective counters advance at trace time, so the per-decode
    static invariant is directly assertable: on (2, 2) one decode trace
    costs mlp=2 psums (ONE for the fused up/gate pair + one for down),
    attn=4 (q/k/v/o), attn_kv=2 (CPU exact-mimic softmax combine); a second
    decode with the same shapes adds zero (no retrace, no extra
    collectives)."""
    _run_forced_4dev(_SPARSE_SETUP + """
    from repro import obs
    obs.configure(enabled=True)
    cfg, sparse = sparse_smoke("llama3.2-1b")
    mesh = make_mesh((2, 2), ("data", "model"))
    eng = ServeEngine(cfg, sparse, slots=2, capacity=32,
                      rules=make_rules(mesh))
    toks = jnp.zeros((2,), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    sites = ("mlp", "attn", "attn_kv", "moe")
    def snap():
        return {s: obs.counter_value("dist.psum", site=s) for s in sites}
    c0 = snap()
    logits, caches = eng._decode(eng.params, toks, eng.caches, pos)
    jax.block_until_ready(logits)
    c1 = snap()
    delta = {s: c1[s] - c0[s] for s in sites}
    assert delta == {"mlp": 2, "attn": 4, "attn_kv": 2, "moe": 0}, delta
    logits, _ = eng._decode(eng.params, toks, caches, pos + 1)
    jax.block_until_ready(logits)
    c2 = snap()
    assert c2 == c1, (c1, c2)
    assert obs.counter_value("dist.psum_bytes", site="mlp") > 0
    assert "dist.psum" in str(obs.summary())
    print("ok")
    """)


def test_tp_padding_edge_replicates_loudly_and_holds_parity():
    """d_ff=72: the packed plane exists (72 % 8 == 0) but 72 % (8*4) != 0,
    so the down kernels cannot K-shard over model=4 - construction warns
    with the leaf path, BOTH components replicate, and the engine still
    matches the replicated oracle token-for-token (the shardable leaves
    keep their shard-mapped route)."""
    _run_forced_4dev(_SPARSE_SETUP + """
    import dataclasses, warnings
    cfg = dataclasses.replace(get_smoke_config("llama3.2-1b"), d_ff=72)
    cfg, sparse = sparse_smoke(None, cfg=cfg)
    prompts = [np.arange(1, 9) % cfg.vocab_size]
    want = serve(cfg, sparse, None, prompts)
    mesh = make_mesh((1, 4), ("data", "model"))
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        got = serve(cfg, sparse, make_rules(mesh), prompts)
    assert any("cannot shard over mesh axis" in str(w.message) for w in rec)
    assert got == want, (got, want)
    print("ok")
    """)


def test_tp_token_parity_moe_expert_banks_4dev():
    """mixtral-smoke expert banks (E, K, N): the down bank K-shards over
    "model" on (1, 4) (one psum for the whole expert grid) and the up/gate
    banks pair-fuse over "data" on (2, 2); both meshes hold token parity
    with the replicated oracle through sliding-window decode."""
    _run_forced_4dev(_SPARSE_SETUP + """
    from repro.dist import sharding as shd
    cfg, sparse = sparse_smoke("mixtral-8x22b")
    mesh = make_mesh((1, 4), ("data", "model"))
    rules = make_rules(mesh)
    tagged = shd.tag_compressed(M.param_axes(cfg), sparse, rules)
    down = None
    def find(kp, leaf):
        global down
        from repro.sparse.formats import SparseTensor
        path = jax.tree_util.keystr(kp)
        if isinstance(leaf, SparseTensor) and "moe" in path \\
                and "down" in path:
            down = leaf
    jax.tree_util.tree_map_with_path(
        find, tagged,
        is_leaf=lambda x: getattr(x, "idx_bits", None) is not None)
    assert down is not None and down.shard is not None, "down bank untagged"
    assert down.shard_site == "moe" and down.k_shard == "model", down.shard
    prompts = [np.arange(1, 9) % cfg.vocab_size,
               (np.arange(2, 10) * 5) % cfg.vocab_size]
    want = serve(cfg, sparse, None, prompts)
    for shape in [(1, 4), (2, 2)]:
        mesh = make_mesh(shape, ("data", "model"))
        got = serve(cfg, sparse, make_rules(mesh), prompts)
        assert got == want, (shape, got, want)
    print("ok")
    """)
