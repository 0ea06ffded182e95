"""Pallas TPU kernel: fused AMAT group-dequant + matmul.

The paper's XPU dequantizes bit-sliced experts in fixed-function hardware
in front of the systolic array.  The TPU-native equivalent fuses the
G32 asymmetric dequant into the matmul at VMEM-tile granularity: a
``(K, bn)`` uint8 code tile is dequantized in VREGs (subtract zp, scale —
and for the MSB-only path, a right-shift on code and zp first) and
immediately fed to the MXU, so the dense weight tile never exists in HBM.

:func:`amat_batched_matmul_pallas` is batched over an expert axis
(``[E, K, N]`` codes) with **per-expert** precision selection: the
``use_lsb`` vector rides in via scalar prefetch
(:class:`pltpu.PrefetchScalarGridSpec`), so expert ``e`` flips between
the MSB+LSB and the MSB-only dequant constants branch-free.  This is the
quantized-execution path of the expert FFN; one weight matrix at a
static precision is the case E=1 (``ops.amat_matmul``).

The codes may carry a leading period axis (``[P, E, K, N]``, the model's
params stacked over its layer scan).  The period to read is a second
scalar-prefetch operand that the BlockSpec index maps return as the
leading block index, so the kernel DMAs its tiles straight out of the
stacked params.  Handing it ``codes[p]`` instead makes XLA copy the
whole period out first: a custom call cannot read a sliced operand in
place.  Unstacked ``[E, K, N]`` codes are the case P=1, period 0.

Tiling (what the TPU compiler accepts, see docs/kernels.md):

* Each block spans the whole contraction axis K, so the group metadata
  block ``(K//G, bn)`` is the full second-minor dimension — legal for
  any group count (a K-tiled ``(bk//G, bn)`` block is not, unless
  ``bk//G`` is a multiple of the dtype's sublane tile).  Expert widths
  keep the ``(K, bn)`` tile small: 2048 x 256 uint8 is 512 KB.
* Grid ``(E, N/bn, M/bm)`` with M innermost: the code tile's block index
  does not change across M tiles, so it is DMA'd once per (e, j).
* uint8 codes and zero-points are widened to int32 before any shift or
  float conversion (Mosaic has no uint8 -> float32 cast).
* The weight tile is cast to the activation dtype before the dot, as the
  dense-dequant path casts its weights (bf16 operands, f32 accumulation
  for a bf16 model).

``interpret=True`` runs the same body on CPU (the tests); on a TPU the
wrappers in ``ops.py`` never set it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dequant_tile(codes, s, z, hi, *, group_size: int, shift: int, dtype):
    """Dequantize a [K, bn] code tile with runtime precision.

    ``hi`` is a scalar bool (this expert's precision): True keeps the
    full high-bit code; False applies the AMAT truncation (shift on code
    *and* zero-point, rescale by ``2**shift``).  The select is on two
    scalars, so both paths cost the same vector work.
    """
    K, bn = codes.shape
    g = K // group_size
    sh = jnp.where(hi, 0, shift).astype(jnp.int32)
    mult = jnp.where(hi, 1.0, float(2 ** shift)).astype(jnp.float32)
    c = (codes.astype(jnp.int32) >> sh).astype(jnp.float32)
    zf = (z.astype(jnp.int32) >> sh).astype(jnp.float32)
    sf = s.astype(jnp.float32) * mult
    w = (c.reshape(g, group_size, bn) - zf[:, None, :]) * sf[:, None, :]
    return w.reshape(K, bn).astype(dtype)


def _amat_batched_kernel(l_ref, u_ref, x_ref, c_ref, s_ref, z_ref, o_ref,
                         *, group_size: int, shift: int):
    hi = u_ref[pl.program_id(0)] > 0                # scalar-prefetched flag
    x = x_ref[0]                                    # [bm, K]
    w = _dequant_tile(c_ref[0, 0], s_ref[0, 0], z_ref[0, 0], hi,
                      group_size=group_size, shift=shift, dtype=x.dtype)
    o_ref[0] = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def amat_batched_matmul_pallas(x, codes, scales, zps, use_lsb, layer=0, *,
                               group_size: int = 32, shift: int = 4,
                               bm: int = 128, bn: int = 256,
                               interpret: bool = False):
    """Per-expert fused dequant-matmul on packed AMAT codes.

    x: [E, M, K]; codes: [E, K, N] uint8, or [P, E, K, N] stacked over
    periods and read at period ``layer`` (a scalar, traced or not);
    scales/zps: [(P,) E, K//G, N]; use_lsb: [E] (bool/int) — expert
    ``e`` computes at high precision iff ``use_lsb[e]``.  Returns
    [E, M, N] f32.  ``N`` must be a multiple of ``bn`` (or
    ``bn >= N``); ``M`` is padded to a multiple of ``bm``.

    ``layer`` and ``use_lsb`` travel via scalar prefetch: they are
    resident in SMEM before the grid starts, so picking the period and
    per-expert precision costs no extra DMA and no grid restructuring —
    DBSC's per-step high/low-bit decisions become per-expert dequant
    shifts inside one kernel launch.
    """
    if codes.ndim == 3:                             # the case P=1
        codes, scales, zps = codes[None], scales[None], zps[None]
    E, M, K = x.shape
    P, N = codes.shape[0], codes.shape[3]
    assert codes.shape == (P, E, K, N), (codes.shape, x.shape)
    assert K % group_size == 0
    assert scales.shape == zps.shape == (P, E, K // group_size, N)
    bm, bn = min(bm, M), min(bn, N)
    assert N % bn == 0, f"pad N to a multiple of bn: {N} vs {bn}"
    m_pad = (-M) % bm
    if m_pad:
        # Padded rows hit zeroed x, contributing nothing.
        x = jnp.pad(x, ((0, 0), (0, m_pad), (0, 0)))
    Mp = M + m_pad
    g = K // group_size
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)
    u = use_lsb.astype(jnp.int32)

    kernel = functools.partial(_amat_batched_kernel, group_size=group_size,
                               shift=shift)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(E, N // bn, Mp // bm),
        in_specs=[
            pl.BlockSpec((1, bm, K), lambda e, j, i, l_ref, u_ref: (e, i, 0)),
            pl.BlockSpec((1, 1, K, bn),
                         lambda e, j, i, l_ref, u_ref: (l_ref[0], e, 0, j)),
            pl.BlockSpec((1, 1, g, bn),
                         lambda e, j, i, l_ref, u_ref: (l_ref[0], e, 0, j)),
            pl.BlockSpec((1, 1, g, bn),
                         lambda e, j, i, l_ref, u_ref: (l_ref[0], e, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda e, j, i, l_ref, u_ref: (e, i, j)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((E, Mp, N), jnp.float32),
        name="amat_expert_matmul",
        interpret=interpret,
    )(lyr, u, x, codes, scales, zps)
    return out[:, :M] if m_pad else out
