"""Fused AMAT group-dequant matmul kernel (batched over experts).

:func:`amat_expert_matmul` is the quantized-execution path of the
expert FFN: packed uint8 codes are dequantized in VREGs inside the
matmul, with per-expert high/low-bit selection delivered by scalar
prefetch — dense expert weights never exist in HBM.
"""

from repro.kernels.amat_matmul.ops import (amat_expert_matmul,
                                           amat_expert_matmul_qt,
                                           amat_matmul, amat_matmul_qt)

__all__ = ["amat_expert_matmul", "amat_expert_matmul_qt", "amat_matmul",
           "amat_matmul_qt"]
