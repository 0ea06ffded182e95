"""Pallas TPU kernel: latent-attention decode (absorbed MLA).

One query token per sequence attends over a latent KV cache whose rows
are ``[c | k_pe]`` (DeepSeek-V2: ``c`` of width ``rank`` = 512, the
rope'd key part of width 64).  With the key up-projection absorbed into
the query, every head scores the same rows, so the cache is read as
multi-query attention: keys are whole rows (576 wide, stored as 640 with
zero columns past 576), values their first ``rank`` columns.

Grid ``(B, S/bs)``, one row per sequence and the cache blocks innermost.
Each step DMAs one ``(bs, R)`` block of the sequence's rows and uses it
for all heads: scores ``q . rowᵀ`` (bf16 operands, f32 accumulation),
an online softmax in VMEM scratch (running max, denominator and the
``[H, rank]`` accumulator), and the value product on the same block.

The valid lengths arrive by scalar prefetch.  The cache block's index
map clamps the block index to the sequence's last valid block, so
blocks past the length repeat the previous index and are not fetched
again, and ``pl.when`` skips their compute.  Rows past the length
inside the last block are masked out of the softmax; they hold finite
values (zeros or a retired sequence's rows), so their zero weights add
nothing to the value product.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _mla_decode_kernel(len_ref, q_ref, kv_ref, o_ref, m_sc, l_sc, acc_sc,
                       *, scale: float, rank: int, bs: int):
    b, j = pl.program_id(0), pl.program_id(1)
    n = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(j * bs < n)
    def _block():
        q = q_ref[0]                                    # [H, R]
        kv = kv_ref[0]                                  # [bs, R]
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, bs]
        row = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(row < n, s, NEG)
        m_prev = m_sc[...]                              # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


def mla_decode_pallas(q, ckv, lengths, *, scale: float, rank: int,
                      bs: int, interpret: bool = False):
    """q: [B, H, R] (cast to the cache's dtype); ckv: [B, S, R] with
    ``S % bs == 0``; lengths: [B] int, each in [1, S].  Returns
    [B, H, rank] f32."""
    B, H, R = q.shape
    S = ckv.shape[1]
    assert ckv.shape == (B, S, R), (ckv.shape, q.shape)
    assert S % bs == 0, f"cache rows {S} not a multiple of the block {bs}"
    kernel = functools.partial(_mla_decode_kernel, scale=scale, rank=rank,
                               bs=bs)

    def kv_index(b, j, len_ref):
        last = jnp.maximum(len_ref[b] - 1, 0) // bs
        return (b, jnp.minimum(j, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, S // bs),
        in_specs=[
            pl.BlockSpec((1, H, R), lambda b, j, len_ref: (b, 0, 0)),
            pl.BlockSpec((1, bs, R), kv_index),
        ],
        out_specs=pl.BlockSpec((1, H, rank), lambda b, j, len_ref: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, rank), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="mla_decode_attention",
        interpret=interpret,
    )(lengths.astype(jnp.int32), q.astype(ckv.dtype), ckv)
