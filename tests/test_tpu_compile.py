"""The expert kernel compiled by the TPU compiler for a described v5e chip
(no chip attached) at Qwen1.5-MoE-A2.7B widths.

Interpret mode on CPU runs the kernel body but never asks the TPU
compiler, which refuses tilings the interpreter accepts.  These tests
compile the served kernel shapes — ``wi`` (K=2048, N=2816) and ``wo``
(K=1408, N=2048) over 60 experts, at decode (M=8) and prefill (M=256)
buffer sizes — and check that the program holds the Pallas kernel.

They also compile a 4-period decode step and prefill at those expert
widths and check that the expert kernel reads its codes straight out of
the stacked ``[P, E, K, N]`` params: no per-period copy of a layer's
codes, scales or zero-points is left in the optimized program.
"""

import dataclasses
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


# One period's expert leaves, as the scan would slice them out of the
# stacked params: wi/wo codes, then their scales and zero-points.
PER_PERIOD_BUFFERS = ("u8[60,2048,2816]", "u8[60,1408,2048]",
                      "f32[60,64,2816]", "u8[60,64,2816]",
                      "f32[60,44,2048]", "u8[60,44,2048]")


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_period_scan_reads_stacked_expert_codes(one_chip, no_compile_cache,
                                                monkeypatch, step):
    from repro.configs.base import get_config
    from repro.core.amat import MatConfig
    from repro.core.slices import quantize_moe_params
    from repro.models import model as MDL
    from repro.models.moe import RoutingPolicy

    # The kernels pick interpret mode from the default backend, the CPU
    # here; this program is compiled for the chip.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    base = get_config("qwen15-moe-repro")
    cfg = dataclasses.replace(
        base, n_layers=4, d_model=2048, n_heads=16, n_kv_heads=16,
        head_dim=128, d_ff=5632, vocab_size=1024, dtype="bfloat16",
        moe=dataclasses.replace(base.moe, n_experts=E, top_k=4, d_ff=1408,
                                d_ff_shared=5632))
    mat = MatConfig(8, 4)

    def specs(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = specs(jax.eval_shape(lambda: quantize_moe_params(
        MDL.init_params(cfg, jax.random.PRNGKey(0)), cfg, mat)[0]))
    B, S = 8, 256
    if step == "decode":
        policy = RoutingPolicy(kind="topk", slice_mode="dbsc",
                               quant_execution=True)
        cache = specs(jax.eval_shape(lambda: MDL.init_cache(cfg, B, S)))
        cache["pos"] = jax.ShapeDtypeStruct((B,), jnp.int32,
                                            sharding=one_chip)
        def fn(p, token, c):
            return MDL.decode_step(p, cfg, token, c, policy=policy, mat=mat,
                                   collect_trace=True)
        args = (params, jax.ShapeDtypeStruct((B,), jnp.int32,
                                             sharding=one_chip), cache)
    else:
        def fn(p, tokens):
            return MDL.prefill(p, cfg, tokens, S, mat=mat,
                               quant_execution=True, collect_trace=True)
        args = (params, jax.ShapeDtypeStruct((1, S), jnp.int32,
                                             sharding=one_chip))
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "u8[4,60,2048,2816]" in hlo
    assert [b for b in PER_PERIOD_BUFFERS if b in hlo] == []
    assert "amat_expert_matmul" in hlo and "tpu_custom_call" in hlo


@pytest.mark.parametrize("batch, rows", [(8, 16896), (1, 512)])
def test_mla_decode_kernel_compiles_for_v5e(one_chip, no_compile_cache,
                                            batch, rows):
    """The latent-attention decode kernel at DeepSeek-V2-Lite widths: 16
    heads over rows of a 512-wide latent and a 64-wide rope part (stored
    in 640 lanes), the cell's batch and cache rows, and one block."""
    from repro.kernels.mla_decode.ops import mla_decode_attention

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(partial(mla_decode_attention, scale=0.1147, rank=512,
                         interpret=False))
    compiled = fn.lower(spec((batch, 16, 576), jnp.float32),
                        spec((batch, rows, 640), jnp.bfloat16),
                        spec((batch,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mla_decode_attention" in text
