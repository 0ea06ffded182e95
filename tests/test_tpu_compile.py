"""The expert kernel compiled by the TPU compiler for a described v5e chip
(no chip attached) at Qwen1.5-MoE-A2.7B widths.

Interpret mode on CPU runs the kernel body but never asks the TPU
compiler, which refuses tilings the interpreter accepts.  These tests
compile the served kernel shapes — ``wi`` (K=2048, N=2816) and ``wo``
(K=1408, N=2048) over 60 experts, at decode (M=8) and prefill (M=256)
buffer sizes — and check that the program holds the Pallas kernel.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.amat_matmul.ops import amat_expert_matmul

E, G = 60, 32
PROJECTIONS = {"wi": (2048, 2816), "wo": (1408, 2048)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    # A compile for a described chip can be written to the persistent
    # cache but not read back without one.
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("proj", sorted(PROJECTIONS))
@pytest.mark.parametrize("m", [8, 256])
def test_expert_kernel_compiles_for_v5e(one_chip, no_compile_cache, proj,
                                        m):
    K, N = PROJECTIONS[proj]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(partial(amat_expert_matmul, group_size=G, shift=4,
                         interpret=False))
    compiled = fn.lower(
        spec((E, m, K), jnp.bfloat16), spec((E, K, N), jnp.uint8),
        spec((E, K // G, N), jnp.float32), spec((E, K // G, N), jnp.uint8),
        spec((E,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
