"""Compile the main path's Pallas kernel for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed next to JAX, compiles
for a chip that is described, not attached.  That refuses what interpret
mode accepts — blocks off the (8, 128) tiling, kernels over the fast-memory
budget — so these compiles guard the serving path at its real widths.
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and it keeps it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.lora_fused.ops import lora_matmul


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# gpt2-small decode (batch 4, one token) and a batch-4 × 128-token prefill,
# through the d_model=768 → 768 LoRA'd attention projections, rank 8
@pytest.mark.parametrize("m", [4, 512])
def test_lora_fused_compiles_for_v5e(one_chip, m):
    k = n = 768
    r = 8

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    fn = jax.jit(lambda x, w, a, b: lora_matmul(x, w, a, b, scale=2.0,
                                                interpret=False))
    compiled = fn.lower(sds(m, k), sds(k, n), sds(k, r), sds(r, n)).compile()
    assert "tpu_custom_call" in compiled.as_text()


# DeepSeek-V2-Lite's held experts (8 of 64, d 2048, width 1408): a decode
# step's rows (8 tokens × 6 picks, padded to 128) and one prefill chunk's
# bound (16,384 tokens × 6), through the gate/up and the down projection
@pytest.mark.parametrize("m,k,n", [(128, 2048, 1408), (128, 1408, 2048),
                                   (98304, 2048, 1408), (98304, 1408, 2048)])
def test_moe_gmm_compiles_for_v5e(one_chip, m, k, n):
    from repro.kernels.moe_gmm.ops import moe_gmm

    def sds(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(lambda x, w, g: moe_gmm(x, w, g, interpret=False))
    compiled = fn.lower(sds(m, k), sds(8, k, n),
                        sds(8, dtype=jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
