"""Real-width TPU compiles of the serving kernels, without a chip.

Each test lowers and compiles a Pallas kernel for one chip of a described
``v5e:2x2`` topology, so Mosaic's refusals (tiling, relayouts, iota widths,
VMEM) surface here instead of on the chip.  The topology is described in a
module fixture - never at import - so every pytest-xdist worker collects the
same tests and only the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.flash_decode import flash_decode_partial
from repro.kernels.nm_spmm import nm_matmul, nm_matmul_expert

LLAMA = get_config("llama3.2-1b")
MIXTRAL = get_config("mixtral-8x22b")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _llama_projections():
    c = LLAMA
    h, kv = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim
    return sorted({(c.d_model, h), (c.d_model, kv), (h, c.d_model),
                   (c.d_model, c.d_ff), (c.d_ff, c.d_model)})


@pytest.mark.parametrize("m", [4, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("kn", _llama_projections(),
                         ids=lambda kn: f"{kn[0]}x{kn[1]}")
def test_nm_matmul_packed2_compiles_at_llama_widths(one_chip, m, kn):
    K, N = kn
    fn = lambda x, v, i: nm_matmul(x, v, i, bm=min(m, 128), bk=512,
                                   bn=256)
    compiled = _compile(fn, one_chip, ((m, K), jnp.bfloat16),
                        ((K // 2, N), jnp.bfloat16), ((K // 8, N), jnp.uint8))
    assert "tpu_custom_call" in compiled.as_text()


def test_nm_matmul_expert_compiles_at_mixtral_expert_slice(one_chip):
    E, M = 2, 8
    K, N = MIXTRAL.d_model, MIXTRAL.moe_d_ff
    assert (K, N) == (6144, 16384)
    fn = lambda x, v, i: nm_matmul_expert(x, v, i, bm=M, bk=512, bn=256)
    compiled = _compile(fn, one_chip, ((E, M, K), jnp.bfloat16),
                        ((E, K // 2, N), jnp.bfloat16),
                        ((E, K // 8, N), jnp.uint8))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_decode_partial_compiles_over_capacity_shard(one_chip):
    """head_dim 64, llama's 8 KV heads x 4 query heads each, over one of
    four shards of a 4096-row cache and of the 256-row cache chip_smoke.py
    serves with (a 64-row shard: one block of the whole shard)."""
    Kh, D = LLAMA.num_kv_heads, LLAMA.head_dim
    G = LLAMA.num_heads // Kh
    for B, C in ((4, 4096 // 4), (4, 256 // 4)):
        compiled = _compile(flash_decode_partial, one_chip,
                            ((B, Kh, G, D), jnp.bfloat16),
                            ((B, C, Kh, D), jnp.bfloat16),
                            ((B, C, Kh, D), jnp.bfloat16),
                            ((B, C), jnp.float32))
        assert "tpu_custom_call" in compiled.as_text(), C
