"""Pure-jnp oracle for the fused AMAT dequant-matmul kernel.

Computes ``x @ dequant(w_q)`` where ``w_q`` is a G-group asymmetric
AMAT-quantized weight.  ``mode`` selects the precision path:
  'high' — full-precision codes:       (q - zp) * s
  'low'  — AMAT truncated (MSB-only):  (q>>shift - zp>>shift) * s * 2^shift
"""

from __future__ import annotations

import jax.numpy as jnp


def amat_matmul_ref(x, codes, scales, zps, *, group_size: int = 32,
                    shift: int = 0, mode: str = "high"):
    """x: [M, K] float; codes: [K, N] uint8; scales/zps: [K//G, N]."""
    K, N = codes.shape
    G = K // group_size
    c = codes.reshape(G, group_size, N).astype(jnp.float32)
    z = zps.reshape(G, 1, N).astype(jnp.float32)
    s = scales.reshape(G, 1, N).astype(jnp.float32)
    if mode == "low" and shift > 0:
        c = jnp.floor(c / (2.0 ** shift))
        z = jnp.floor(z / (2.0 ** shift))
        s = s * (2.0 ** shift)
    w = ((c - z) * s).reshape(K, N)
    return x.astype(jnp.float32) @ w


def _dequant_mixed_ref(codes, scales, zps, use_lsb, *, group_size, shift):
    """[E, K, N] codes -> [E, K, N] f32 weights, per-expert precision."""
    E, K, N = codes.shape
    G = K // group_size
    c = codes.reshape(E, G, group_size, N).astype(jnp.float32)
    z = zps.reshape(E, G, 1, N).astype(jnp.float32)
    s = scales.reshape(E, G, 1, N).astype(jnp.float32)
    w_hi = (c - z) * s
    w_lo = (jnp.floor(c / (2.0 ** shift)) - jnp.floor(z / (2.0 ** shift))) \
        * (s * (2.0 ** shift))
    sel = use_lsb.reshape(E, 1, 1, 1).astype(bool)
    return jnp.where(sel, w_hi, w_lo).reshape(E, K, N)


def amat_batched_matmul_ref(x, codes, scales, zps, use_lsb, *,
                            group_size: int = 32, shift: int = 4):
    """x: [E, M, K]; codes: [E, K, N]; scales/zps: [E, K//G, N];
    use_lsb: [E] bool.  Returns [E, M, N] f32."""
    w = _dequant_mixed_ref(codes, scales, zps, use_lsb,
                           group_size=group_size, shift=shift)
    return jnp.einsum("emk,ekn->emn", x.astype(jnp.float32), w)
