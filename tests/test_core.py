"""UniPruning core: metrics, masks, prox, mirror-descent invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import ModelConfig, PruneConfig
from repro.core import calibrate, masks as masks_mod, metrics as metrics_mod
from repro.core import mirror, prox
from repro.core.prunable import prunable_map
from repro.data.synthetic import batches_for
from repro.models import model as M

TINY = ModelConfig(name="t", family="dense", d_model=64, num_layers=2,
                   num_heads=2, num_kv_heads=2, head_dim=32, d_ff=128,
                   vocab_size=256)



@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 700),
       levels=st.sampled_from([0, 2, 5, 1000]),
       frac=st.floats(0.0, 1.0), subnormal=st.booleans())
def test_kth_smallest_nonneg_equals_sort(seed, n, levels, frac, subnormal):
    """The bisection median is the sort's k-th element on non-negative data
    with ties (few distinct levels), zeros and -0.0: the same value.  With
    subnormals in the data the two agree as the device compares floats
    (it compares a subnormal as zero)."""
    rng = np.random.default_rng(seed)
    x = np.abs(rng.standard_normal(n)).astype(np.float32)
    if levels:
        x = np.round(x * levels) / levels          # ties (and zeros)
    x[rng.random(n) < 0.2] = 0.0
    x[rng.random(n) < 0.1] = -0.0
    if subnormal:
        x[rng.random(n) < 0.1] = np.float32(1e-40)
    k = min(int(frac * n), n - 1)
    got = metrics_mod.kth_smallest_nonneg(jnp.asarray(x), k)
    want = jnp.sort(jnp.asarray(x))[k]
    assert bool(got == want), (k, float(got), float(want))
    if not subnormal:
        assert float(got) == float(want), (k, float(got), float(want))


def test_normalize_scores_median_stacked_leaf_matches_sort():
    """A stacked (layers, K, N) score leaf is scaled by the sort median of
    all its elements, bit for bit; a leaf of 2**31 elements is refused."""
    s = jnp.abs(jax.random.normal(jax.random.key(3), (3, 32, 24)))
    s = jnp.round(s * 4) / 4                       # ties and zeros
    flat = s.reshape(-1)
    want = s / (jnp.sort(flat)[flat.size // 2] + 1e-12)
    got = metrics_mod.normalize_scores(s, "median")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    big = jax.ShapeDtypeStruct((2, 2 ** 30), jnp.float32)
    with pytest.raises(ValueError, match="int32"):
        jax.eval_shape(lambda a: metrics_mod.normalize_scores(a, "median"),
                       big)

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), scale=st.floats(0.1, 10.0))
def test_metric_scale_behaviour(seed, scale):
    w = jax.random.normal(jax.random.key(seed), (32, 16))
    a = jnp.abs(jax.random.normal(jax.random.key(seed + 1), (32,)))
    # wanda scales linearly in W; RIA is scale-invariant in W
    np.testing.assert_allclose(metrics_mod.wanda(scale * w, a),
                               scale * metrics_mod.wanda(w, a), rtol=1e-5)
    np.testing.assert_allclose(metrics_mod.ria(scale * w, a),
                               metrics_mod.ria(w, a), rtol=1e-4, atol=1e-6)


def test_stochria_full_frac_equals_ria():
    w = jax.random.normal(jax.random.key(0), (32, 16))
    a = jnp.abs(jax.random.normal(jax.random.key(1), (32,)))
    s1 = metrics_mod.stochria(w, a, key=jax.random.key(2), frac=1.0)
    s2 = metrics_mod.ria(w, a)
    np.testing.assert_allclose(s1, s2, rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(sp=st.floats(0.05, 0.95), seed=st.integers(0, 1000))
def test_unstructured_mask_exact_sparsity(sp, seed):
    tree = {"a": jax.random.normal(jax.random.key(seed), (64, 32)),
            "b": jax.random.normal(jax.random.key(seed + 1), (128, 16))}
    m = masks_mod.unstructured_masks(tree, sp, scope="global")
    got = masks_mod.sparsity_of(m)
    assert abs(got - sp) < 0.02, (got, sp)


@settings(max_examples=10, deadline=None)
@given(n=st.sampled_from([1, 2, 3]), m=st.sampled_from([4, 8]),
       seed=st.integers(0, 1000))
def test_nm_mask_constraint(n, m, seed):
    s = jax.random.normal(jax.random.key(seed), (64, 32))
    mask = jax.tree.leaves(masks_mod.nm_masks(s, n, m))[0]
    per_group = mask.reshape(64 // m, m, 32).sum(axis=1)
    assert bool(jnp.all(per_group == n))
    # kept entries are the group top-n by |s|
    grp = jnp.abs(s).reshape(64 // m, m, 32)
    kept_min = jnp.min(jnp.where(mask.reshape(64 // m, m, 32), grp, jnp.inf),
                       axis=1)
    dropped_max = jnp.max(
        jnp.where(mask.reshape(64 // m, m, 32), -jnp.inf, grp), axis=1)
    assert bool(jnp.all(kept_min >= dropped_max))


def test_threshold_bisect_matches_quantile():
    tree = {"a": jax.random.normal(jax.random.key(0), (512, 64))}
    for sp in [0.3, 0.6, 0.9]:
        t1 = float(masks_mod.global_threshold(tree, sp))
        t2 = float(masks_mod.threshold_bisect(tree, sp, iters=45))
        m = masks_mod.unstructured_masks(tree, sp, scope="global",
                                         exact=False)
        got = masks_mod.sparsity_of(m)
        assert abs(got - sp) < 5e-3, (sp, got)
        assert abs(t1 - t2) / (abs(t1) + 1e-9) < 1e-2


@settings(max_examples=20, deadline=None)
@given(lam=st.floats(0, 2, width=32), x=st.floats(-5, 5, width=32))
def test_soft_threshold_properties(lam, x):
    x = float(np.float32(x))  # the op runs in f32; avoid f64 subnormals
    lam = float(np.float32(lam))
    y = float(prox.soft_threshold(jnp.asarray(x), lam))
    assert abs(y) <= abs(x) + 1e-6
    if abs(x) <= lam:
        assert y == 0.0
    else:
        assert np.sign(y) == np.sign(x)
        assert abs(abs(y) - (abs(x) - lam)) < 1e-5


def test_prunable_map_excludes_embeddings():
    params = M.init_params(TINY, jax.random.key(0))
    pm = prunable_map(params)
    flat, _ = jax.tree_util.tree_flatten_with_path(pm)
    for kp, v in flat:
        path = jax.tree_util.keystr(kp)
        if "embed" in path or "norm" in path.lower():
            assert not v, path
        if "attn" in path and "kernel" in path and "norm" not in path:
            assert v, path


def _search_setup(steps=6, **kw):
    params = M.init_params(TINY, jax.random.key(0))
    calib = batches_for(TINY, n=4, batch=2, seq=32, split="calib")
    stats = calibrate.collect_stats(TINY, params, calib[:2])
    pcfg = PruneConfig(local_metric="wanda", steps=steps, **kw)
    return params, calib, stats, pcfg


def test_search_state_evolves_and_w0_untouched():
    params, calib, stats, pcfg = _search_setup()
    before = jax.tree.map(lambda x: np.asarray(x).copy(), params)
    state, hist = calibrate.run_search(TINY, pcfg, params, calib, stats,
                                       log_every=1)
    # W0 untouched
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, np.asarray(b))
    # Gamma engaged
    nz = sum(int(jnp.sum(g != 0)) for g in
             jax.tree.leaves(state.Gamma, is_leaf=lambda x: x is None)
             if g is not None)
    assert nz > 0
    assert int(state.step) == pcfg.steps


def test_one_shot_masks_nested_across_sparsity():
    """Higher sparsity mask must be a subset of lower sparsity mask."""
    params, calib, stats, pcfg = _search_setup()
    state, _ = calibrate.run_search(TINY, pcfg, params, calib, stats)
    m50 = mirror.export_masks(pcfg, state.Gamma, 0.5, V=state.V)
    m70 = mirror.export_masks(pcfg, state.Gamma, 0.7, V=state.V)
    for a, b in zip(jax.tree.leaves(m50, is_leaf=lambda x: x is None),
                    jax.tree.leaves(m70, is_leaf=lambda x: x is None)):
        if a is None:
            continue
        assert bool(jnp.all(jnp.where(b, a, True)))  # b => a


def test_nm_mode_produces_24_masks():
    params, calib, stats, pcfg = _search_setup(mode="nm")
    state, _ = calibrate.run_search(TINY, pcfg, params, calib, stats)
    masks = mirror.export_masks(pcfg, state.Gamma, 0.5, V=state.V)
    for mk in jax.tree.leaves(masks, is_leaf=lambda x: x is None):
        if mk is None:
            continue
        arr = np.asarray(mk)
        arr = arr.reshape(-1, 4, arr.shape[-1]) if arr.shape[0] % 4 == 0 \
            else None
        if arr is not None:
            assert (arr.sum(axis=1) == 2).all()


def test_apply_masks_zeroes_only_masked():
    params, calib, stats, pcfg = _search_setup(steps=3)
    state, _ = calibrate.run_search(TINY, pcfg, params, calib, stats)
    masks = mirror.export_masks(pcfg, state.Gamma, 0.6, V=state.V)
    pruned = masks_mod.apply_masks(params, masks)
    flat_m = jax.tree.leaves(masks, is_leaf=lambda x: x is None)
    for w0, w1, mk in zip(jax.tree.leaves(params), jax.tree.leaves(pruned),
                          flat_m):
        if mk is None:
            np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))
        else:
            np.testing.assert_array_equal(
                np.asarray(w1), np.asarray(w0 * mk.astype(w0.dtype)))
