"""Jit'd public wrappers for the fused AMAT dequant-matmul kernel.

Handle padding to block multiples, backend detection (interpret=True on
CPU — executes the kernel body in Python for correctness validation; on
TPU the same BlockSpecs drive real VMEM tiling) and the QuantizedTensor
calling convention.  ``amat_expert_matmul`` is the quantized-execution
entry point the MoE layer calls on the ``[E, C, d]`` dispatch buffer
(see docs/kernels.md).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.amat_matmul.kernel import amat_batched_matmul_pallas
from repro.quant.groupquant import QuantizedTensor


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("group_size", "shift", "bm", "bn",
                                   "interpret"))
def amat_expert_matmul(x, codes, scales, zps, use_lsb, layer=0, *,
                       group_size: int = 32, shift: int = 4,
                       bm: int = 128, bn: int = 256,
                       interpret: bool | None = None):
    """[E, M, K] @ per-expert-dequant([E, K, N] codes) -> [E, M, N] f32.

    ``codes``/``scales``/``zps`` may be stacked over periods
    (``[P, E, K, N]``); the kernel then reads period ``layer`` in place.
    ``use_lsb`` [E] selects MSB+LSB (high-bit) vs MSB-only dequant per
    expert inside the kernel.  M is padded in-kernel; N is padded here
    to a multiple of ``bn`` (zero scales null the pad region).  Expert
    widths are multiples of 128, so the served path never pads.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    N = codes.shape[-1]
    bn_ = min(bn, N)
    out = amat_batched_matmul_pallas(
        x, _pad_to(codes, bn_, -1), _pad_to(scales, bn_, -1),
        _pad_to(zps, bn_, -1), use_lsb, layer, group_size=group_size,
        shift=shift, bm=bm, bn=bn_, interpret=interpret)
    return out[:, :, :N]


def amat_expert_matmul_qt(x, qt: QuantizedTensor, use_lsb, layer=0, *,
                          shift: int, **kw):
    """QuantizedTensor convention for the batched expert kernel."""
    assert qt.asymmetric, "AMAT kernel expects asymmetric group quant"
    return amat_expert_matmul(x, qt.codes, qt.scales, qt.zero_points,
                              use_lsb, layer, group_size=qt.group_size,
                              shift=shift, **kw)


def amat_matmul(x, codes, scales, zps, *, group_size: int = 32,
                shift: int = 0, mode: str = "high", **kw):
    """x [M, K] @ dequant(codes [K, N]) -> [M, N] f32 at a static
    precision: the expert kernel with one expert."""
    hi = jnp.asarray([mode == "high"])
    return amat_expert_matmul(x[None], codes[None], scales[None],
                              zps[None], hi, group_size=group_size,
                              shift=shift, **kw)[0]


def amat_matmul_qt(x, qt: QuantizedTensor, *, shift: int = 0,
                   mode: str = "high", **kw):
    assert qt.asymmetric, "AMAT kernel expects asymmetric group quant"
    return amat_matmul(x, qt.codes, qt.scales, qt.zero_points,
                       group_size=qt.group_size, shift=shift, mode=mode,
                       **kw)
