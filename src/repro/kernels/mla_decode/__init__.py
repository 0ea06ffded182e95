"""Latent-attention decode kernel (DeepSeek-V2's absorbed MLA decode).

:func:`mla_decode_attention` attends one query token per sequence, all
heads at once, over a latent KV cache whose rows are ``[c | k_pe]``:
keys are the whole row, values its first ``rank`` columns.
"""

from repro.kernels.mla_decode.ops import (MLA_BLOCK, mla_cache_rows,
                                          mla_cache_width,
                                          mla_decode_attention)

__all__ = ["MLA_BLOCK", "mla_cache_rows", "mla_cache_width",
           "mla_decode_attention"]
