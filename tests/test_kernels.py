"""Pallas kernels vs pure-jnp oracles (interpret mode) with hypothesis
shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.prox import prox_nm24, prox_nm24_ref
from repro.kernels import ref
from repro.kernels.nm_prox import nm_mask24, prox24
from repro.kernels.nm_spmm import nm_matmul
from repro.kernels.saliency_fuse import saliency_fused_step

SHAPES = st.sampled_from([(64, 128), (128, 128), (256, 384), (64, 256)])
DTYPES = st.sampled_from([jnp.float32, jnp.bfloat16])


@settings(max_examples=8, deadline=None)
@given(kn=SHAPES, dtype=DTYPES, seed=st.integers(0, 10_000))
def test_nm_matmul_matches_ref(kn, dtype, seed):
    K, N = kn
    M = 32
    w = jax.random.normal(jax.random.key(seed), (K, N), jnp.float32)
    vals, idx = ref.compress_24(w)
    vals = vals.astype(dtype)
    x = (0.1 * jax.random.normal(jax.random.key(seed + 1), (M, K),
                                 jnp.float32)).astype(dtype)
    y = nm_matmul(x, vals, idx, bm=32, bk=64, bn=128, interpret=True)
    yr = ref.nm_matmul_ref(x, vals, idx)
    rtol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(y.astype(jnp.float32),
                               yr.astype(jnp.float32), rtol=rtol, atol=rtol)


@settings(max_examples=8, deadline=None)
@given(kn=SHAPES, dtype=DTYPES, seed=st.integers(0, 10_000))
def test_nm_matmul_packed2_bit_exact_vs_int8(kn, dtype, seed):
    """Kernel-native 2-bit-packed index tiles (unpacked in VMEM after the
    copy) must match the int8 index plane bit-for-bit across TPU-shaped
    tilings (grid > 1 in every dim) in interpret mode."""
    from repro.sparse.formats import _pack_idx2
    K, N = kn
    M = 32
    w = jax.random.normal(jax.random.key(seed), (K, N), jnp.float32)
    vals, idx = ref.compress_24(w)
    vals = vals.astype(dtype)
    packed = _pack_idx2(idx)
    x = (0.1 * jax.random.normal(jax.random.key(seed + 1), (M, K),
                                 jnp.float32)).astype(dtype)
    y8 = nm_matmul(x, vals, idx, bm=16, bk=32, bn=128, layout="int8",
                   interpret=True)
    y2 = nm_matmul(x, vals, packed, bm=16, bk=32, bn=128, layout="packed2",
                   interpret=True)
    np.testing.assert_array_equal(np.asarray(y8), np.asarray(y2))
    # and layout inference from the index-plane shape picks the same path
    y2i = nm_matmul(x, vals, packed, bm=16, bk=32, bn=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(y2i))


def test_nm_matmul_packed2_matches_masked_dense_single_tile():
    """The CPU serving configuration (``sparse.apply._run_nm``: decompress,
    one dense dot) stays bit-exact vs the masked-dense fp32 dot; the Pallas
    kernel, which sums four partial dots, agrees to fp32 rounding."""
    from repro.sparse.apply import _run_nm
    from repro.sparse.formats import _pack_idx2
    K, N, M = 64, 48, 4
    w = jax.random.normal(jax.random.key(11), (K, N), jnp.float32)
    m = ref.nm_mask_ref(w)
    vals, idx = ref.compress_24(w * m)
    x = 0.1 * jax.random.normal(jax.random.key(12), (M, K), jnp.float32)
    want = jnp.dot(x, w * m, preferred_element_type=jnp.float32)
    y = _run_nm(x, vals, _pack_idx2(idx), "packed2")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    yk = nm_matmul(x, vals, _pack_idx2(idx), bm=M, bk=K, bn=N,
                   layout="packed2", interpret=True)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_compress_roundtrip_preserves_24_weights():
    w = jax.random.normal(jax.random.key(0), (128, 64))
    m = ref.nm_mask_ref(w)
    w24 = w * m
    vals, idx = ref.compress_24(w24)
    np.testing.assert_allclose(ref.decompress_24(vals, idx), w24, rtol=1e-6)


def test_compressed_bytes_ratio():
    K, N = 1024, 1024
    dense_bytes = K * N * 2                      # bf16
    comp_bytes = (K // 2) * N * 2 + (K // 2) * N  # bf16 vals + int8 idx
    assert comp_bytes / dense_bytes == 0.75
    packed = (K // 2) * N * 2 + (K // 2) * N // 4  # 2-bit packed idx
    assert packed / dense_bytes == 0.5625


@settings(max_examples=6, deadline=None)
@given(kn=SHAPES, metric=st.sampled_from(["wanda", "ria", "magnitude"]),
       seed=st.integers(0, 1000))
def test_saliency_fuse_matches_ref(kn, metric, seed):
    K, N = kn
    key = jax.random.key(seed)
    w = jax.random.normal(key, (K, N))
    a = jnp.abs(jax.random.normal(jax.random.key(seed + 1), (K,))) * 5
    g = 0.1 * jax.random.normal(jax.random.key(seed + 2), (K, N))
    v = 0.1 * jax.random.normal(jax.random.key(seed + 3), (K, N))
    rows = jnp.sum(jnp.abs(w), 1)
    cols = jnp.sum(jnp.abs(w), 0)
    kw = dict(rowsum=rows, colsum=cols) if metric == "ria" else {}
    v2, g2 = saliency_fused_step(w, a, g, v, metric=metric, interpret=True,
                                 bk=64, bn=128, **kw)
    if metric == "wanda":
        vr, gr = ref.saliency_step_ref(w, a, g, v, v_lr=0.1, lam=1e-3)
    elif metric == "magnitude":
        vr, gr = ref.saliency_step_ref(w, jnp.ones_like(a), g, v, v_lr=0.1,
                                       lam=1e-3)
    else:
        vr, gr = ref.saliency_step_ref(w, a, g, v, v_lr=0.1, lam=1e-3,
                                       rowsum=rows[:, None],
                                       colsum=cols[None, :])
    np.testing.assert_allclose(v2, vr, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(g2, gr, rtol=1e-5, atol=1e-6)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 1000), lam=st.sampled_from([0.0, 0.01, 0.05, 0.5]))
def test_prox24_kernel_matches_core(seed, lam):
    w = jax.random.normal(jax.random.key(seed), (64, 128))
    p1 = prox24(w, lam=lam, interpret=True, bk=32, bn=128)
    p2 = prox_nm24(w, lam)
    np.testing.assert_allclose(p1, p2, rtol=1e-5, atol=1e-6)


def test_prox24_against_bruteforce_oracle():
    w = jax.random.normal(jax.random.key(7), (16, 8))
    np.testing.assert_allclose(prox_nm24(w, 0.05), prox_nm24_ref(w, 0.05),
                               rtol=1e-3, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), ties=st.booleans())
def test_nm_mask24_kernel_matches_ref(seed, ties):
    w = jax.random.normal(jax.random.key(seed), (64, 128))
    if ties:
        w = jnp.round(w * 2) / 2
    m1 = nm_mask24(w, interpret=True, bk=32, bn=128)
    m2 = ref.nm_mask_ref(w)
    assert bool(jnp.all(m1 == m2))
    assert bool(jnp.all(m1.reshape(16, 4, 128).sum(1) == 2))


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), lam=st.floats(0.0, 1.0))
def test_prox24_properties(seed, lam):
    """Shrinkage (|out| <= |w|), sign preservation, lam=0 identity."""
    w = jax.random.normal(jax.random.key(seed), (32, 16))
    out = prox_nm24(w, lam)
    assert bool(jnp.all(jnp.abs(out) <= jnp.abs(w) + 1e-6))
    nz = jnp.abs(out) > 0
    assert bool(jnp.all(jnp.where(nz, jnp.sign(out) == jnp.sign(w), True)))
    if lam == 0.0:
        np.testing.assert_allclose(out, w, rtol=1e-6)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 1000),
       dims=st.sampled_from([(2, 2, 4, 32, 128), (1, 1, 8, 64, 256),
                             (2, 4, 1, 32, 64)]))
def test_flash_decode_matches_ref(seed, dims):
    from repro.kernels.flash_decode import flash_decode, flash_decode_ref
    B, K, G, D, C = dims
    q = 0.5 * jax.random.normal(jax.random.key(seed), (B, K, G, D))
    k = 0.5 * jax.random.normal(jax.random.key(seed + 1), (B, C, K, D))
    v = 0.5 * jax.random.normal(jax.random.key(seed + 2), (B, C, K, D))
    valid = jax.random.randint(jax.random.key(seed + 3), (), C // 2, C + 1)
    bias = jnp.where(jnp.arange(C)[None, :] < valid, 0.0, -1e30) * \
        jnp.ones((B, 1))
    y = flash_decode(q, k, v, bias, bc=32, interpret=True)
    yr = flash_decode_ref(q, k, v, bias)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=2e-4,
                               atol=2e-5)



@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 1000),
       dims=st.sampled_from([(2, 2, 4, 32, 128), (1, 1, 8, 64, 256),
                             (4, 8, 4, 64, 64)]),
       shards=st.sampled_from([1, 2, 4]))
def test_flash_decode_partial_matches_ref(seed, dims, shards):
    """The partial kernel's raw (acc, m, l) match the materialized oracle on
    every capacity shard, an all-masked shard included, and the shards
    combine (pmax on m, sum of rescaled l/acc) into the full attention."""
    from repro.kernels.flash_decode import (flash_decode_partial,
                                            flash_decode_partial_ref,
                                            flash_decode_ref)
    B, K, G, D, C = dims
    q = 0.5 * jax.random.normal(jax.random.key(seed), (B, K, G, D))
    k = 0.5 * jax.random.normal(jax.random.key(seed + 1), (B, C, K, D))
    v = 0.5 * jax.random.normal(jax.random.key(seed + 2), (B, C, K, D))
    # valid prefix ends inside the first shard: the later shards of a
    # multi-shard split are all masked
    valid = jax.random.randint(jax.random.key(seed + 3), (), 1,
                               C // shards + 1)
    bias = jnp.where(jnp.arange(C)[None, :] < valid, 0.0, -1e30) * \
        jnp.ones((B, 1))
    cs = C // shards
    parts = []
    for i in range(shards):
        sl = slice(i * cs, (i + 1) * cs)
        got = flash_decode_partial(q, k[:, sl], v[:, sl], bias[:, sl],
                                   bc=min(32, cs), interpret=True)
        want = flash_decode_partial_ref(q, k[:, sl], v[:, sl], bias[:, sl])
        for g, w, name in zip(got, want, ("acc", "m", "l")):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-5, err_msg=name)
        parts.append(got)
    mg = jnp.max(jnp.stack([m for _, m, _ in parts]), axis=0)
    l = sum(l_ * jnp.exp(m - mg) for _, m, l_ in parts)
    acc = sum(a * jnp.exp(m - mg) for a, m, _ in parts)
    np.testing.assert_allclose(np.asarray(acc / l),
                               np.asarray(flash_decode_ref(q, k, v, bias)),
                               rtol=2e-4, atol=2e-5)

def test_ops_sparse_dense_roundtrip():
    """A 2-bit-packed bf16 2:4 leaf through the serving dispatch
    (``sparse.apply.sparse_dense``) reproduces x @ (W * mask)."""
    from repro.sparse import apply as apply_mod
    from repro.sparse.pack import pack_nm
    w = jax.random.normal(jax.random.key(0), (128, 64))
    m = ref.nm_mask_ref(w)
    st = pack_nm(w, m, idx_bits=2, dtype=jnp.bfloat16)
    assert st.kernel_layout == "packed2"
    x = 0.1 * jax.random.normal(jax.random.key(1), (8, 128))
    y = apply_mod.sparse_dense(st, x)
    np.testing.assert_allclose(
        np.asarray(y, np.float32),
        np.asarray(x @ (w * m).astype(jnp.bfloat16), np.float32),
        rtol=3e-2, atol=3e-3)
