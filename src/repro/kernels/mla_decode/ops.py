"""Jit'd public wrapper for the latent-attention decode kernel, and the
shape of a latent cache it reads: whole blocks of rows, whole lanes of
row values."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.mla_decode.kernel import mla_decode_pallas

# Cache rows per kernel block.
MLA_BLOCK = 512
# Cache row width unit: the TPU's lane count.  A row of 576 values
# (DeepSeek-V2's 512 + 64) is stored as 640; at 576 the compiler tiles
# the cache with the rows, not the row values, on the lanes, and copies
# each layer's cache into the kernel's layout and back every step.
LANES = 128


def mla_cache_rows(max_seq: int) -> int:
    """Rows to allocate for ``max_seq`` positions: a whole number of
    kernel blocks (of ``MLA_BLOCK`` rows, or of 16 below one block)."""
    unit = MLA_BLOCK if max_seq > MLA_BLOCK else 16
    return -(-max_seq // unit) * unit


def mla_cache_width(width: int) -> int:
    """Values to allocate for a latent row of ``width``: whole lanes; the
    columns past ``width`` stay zero."""
    return -(-width // LANES) * LANES


@partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def mla_decode_attention(q, ckv, lengths, *, scale: float, rank: int,
                         interpret: bool | None = None):
    """Absorbed latent-attention decode: q [B, H, R] against the latent
    cache ckv [B, S, W] (rows ``[c | k_pe]``, zero past R <= W), each
    sequence over its first ``lengths[b]`` rows.  Returns [B, H, rank]
    f32."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    S, W = ckv.shape[1:]
    q = jnp.pad(q, ((0, 0), (0, 0), (0, W - q.shape[-1])))
    return mla_decode_pallas(q, ckv, lengths, scale=scale, rank=rank,
                             bs=min(MLA_BLOCK, S), interpret=interpret)
