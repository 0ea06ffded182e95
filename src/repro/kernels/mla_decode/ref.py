"""Pure-jnp oracle for the latent-attention decode kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mla_decode_attention_ref(q, ckv, lengths, *, scale: float, rank: int):
    """q: [B, H, R] (R = rank + rope width); ckv: [B, S, R] latent rows
    ``[c | k_pe]``; lengths: [B] valid rows.  Returns [B, H, rank] f32:
    softmax over each sequence's valid rows of ``q . row * scale``,
    weighting the rows' first ``rank`` columns."""
    kv = ckv.astype(jnp.float32)
    s = jnp.einsum("bhr,bsr->bhs", q.astype(jnp.float32), kv) * scale
    valid = jnp.arange(ckv.shape[1])[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p, kv[..., :rank])
