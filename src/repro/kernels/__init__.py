"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

Every kernel ships as a triple — ``kernel.py`` (the Pallas body +
``pallas_call`` wiring), ``ops.py`` (jit'd public wrapper: padding,
interpret-mode backend detection, ``QuantizedTensor`` convention) and
``ref.py`` (a pure-jnp oracle the tests compare against bit-for-bit).
On CPU the wrappers select ``interpret=True`` so CI executes the same
kernel bodies the TPU runs; see docs/kernels.md for the grid/BlockSpec
and tiling constraints of each kernel.

Subpackages:
  * :mod:`repro.kernels.amat_matmul` — fused AMAT group-dequant matmuls,
    including the batched-expert quantized-execution kernels (per-expert
    ``use_lsb`` via scalar prefetch) used by the MoE decode hot path.
  * :mod:`repro.kernels.expert_matmul` — the original batched per-expert
    sliced dequant matmul (per-expert flag as a VMEM block).
  * :mod:`repro.kernels.flash_attn` — blockwise online-softmax attention.
  * :mod:`repro.kernels.mla_decode` — latent-attention decode over a
    latent KV cache (DeepSeek-V2's absorbed MLA), the served decode path
    of ``attn_kind="mla"``.
"""
