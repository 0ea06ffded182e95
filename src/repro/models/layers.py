"""Transformer building blocks (pure JAX, shard-friendly).

Everything here is written against stacked-parameter pytrees so the model
stack can ``lax.scan`` over layers, and against explicit shapes so the
dry-run can lower every (arch x input-shape) pair without allocation.

Covers the assigned architecture pool:
  * RMSNorm / LayerNorm
  * RoPE (configurable theta, partial-dim for Mamba-hybrids), and YaRN
    frequency scaling with DeepSeek-V2's interleaved rope pairs
  * GQA attention with optional sliding window and logit soft-capping,
    causal or full (encoder), plus cross-attention (whisper)
  * Blockwise ("flash-style") attention via lax.scan over KV chunks, used
    automatically above a sequence-length threshold so 32k prefill never
    materializes an (S x S) score matrix
  * Single-token decode attention against a KV cache
  * MLP variants: SwiGLU (llama-family), GeGLU (gemma), squared-ReLU
    (nemotron), GELU (starcoder2/whisper)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

# Sequence length above which attention switches to the blockwise
# (online-softmax) implementation.
BLOCKWISE_THRESHOLD = 8192
BLOCK_KV = 1024


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(dtype)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, D]; positions: broadcastable to [..., S]."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                      # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(angles)[..., None, :]                     # [..., S, 1, D/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """YaRN rope scaling (DeepSeek-V2's ``rope_scaling`` of type yarn)."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """``g(s, mu) = 0.1 * mu * ln(s) + 1`` (1 for ``s <= 1``)."""
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def yarn_inv_freq(dim: int, theta: float, ys: YarnScaling) -> jax.Array:
    """Inverse frequencies of ``dim`` rope dims under YaRN: the unscaled
    frequency below the correction range, the frequency over ``factor``
    above it, and a linear ramp between."""
    def correction_dim(rotations):
        return dim * math.log(ys.original_max_position
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(correction_dim(ys.beta_fast)), 0)
    hi = min(math.ceil(correction_dim(ys.beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    extra = rope_frequencies(dim, theta)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra / ys.factor * (1.0 - keep) + extra * keep


def yarn_attention_factor(ys: YarnScaling) -> float:
    """The factor YaRN puts on cos and sin."""
    return yarn_mscale(ys.factor, ys.mscale) \
        / yarn_mscale(ys.factor, ys.mscale_all_dim)


def mla_softmax_scale(qk_head_dim: int,
                      ys: Optional[YarnScaling]) -> float:
    """Latent attention's softmax scale: ``qk_head_dim ** -0.5``, times
    ``g(factor, mscale_all_dim) ** 2`` under YaRN (DeepSeek-V2)."""
    scale = qk_head_dim ** -0.5
    if ys is not None and ys.mscale_all_dim:
        scale *= yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


def apply_rope_interleaved(x: jax.Array, positions: jax.Array,
                           inv_freq: jax.Array,
                           factor: float = 1.0) -> jax.Array:
    """RoPE over rope pairs stored interleaved (DeepSeek-V2): the last
    axis of x [..., S, H, D] is de-interleaved (even dims, then odd
    ones) and rotated half against half; the result stays de-interleaved.
    ``factor`` scales cos and sin."""
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(angles) * factor)[..., None, :]
    sin = (jnp.sin(angles) * factor)[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AttnDims:
    n_heads: int
    n_kv_heads: int
    head_dim: int

    @property
    def q_rep(self) -> int:
        return self.n_heads // self.n_kv_heads


def _soft_cap(scores: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return scores
    return cap * jnp.tanh(scores / cap)


def _expand_kv(k: jax.Array, rep: int) -> jax.Array:
    """[B, S, Hkv, D] -> [B, S, Hkv*rep, D] by repeat (GQA)."""
    if rep == 1:
        return k
    b, s, hkv, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, hkv, rep, d)) \
              .reshape(b, s, hkv * rep, d)


def attention(
    q: jax.Array,               # [B, Sq, H, D]
    k: jax.Array,               # [B, Sk, Hkv, D]
    v: jax.Array,               # [B, Sk, Hkv, D]
    *,
    causal: bool,
    q_offset: int | jax.Array = 0,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Multi-head attention; dispatches to blockwise above the threshold.

    ``v`` may be narrower than ``q``/``k`` (latent attention's expanded
    form); ``scale`` defaults to ``q.shape[-1] ** -0.5``."""
    if k.shape[1] > BLOCKWISE_THRESHOLD:
        return blockwise_attention(
            q, k, v, causal=causal, q_offset=q_offset,
            sliding_window=sliding_window, logit_softcap=logit_softcap,
            scale=scale)

    dims_rep = q.shape[2] // k.shape[2]
    k = _expand_kv(k, dims_rep)
    v = _expand_kv(v, dims_rep)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = _soft_cap(scores, logit_softcap)

    sq, sk = q.shape[1], k.shape[1]
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if sliding_window is not None:
        mask &= qpos[:, None] - kpos[None, :] < sliding_window
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def blockwise_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool,
    q_offset: int | jax.Array = 0,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    block_kv: int = BLOCK_KV,
    scale: Optional[float] = None,
) -> jax.Array:
    """Online-softmax attention: lax.scan over KV blocks.

    Never materializes the (Sq x Sk) score matrix — peak memory is
    (Sq x block_kv) per head.  This is flash-attention at the HLO level;
    the Pallas kernel variant lives in repro/kernels.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    rep = h // k.shape[2]
    pad = (-sk) % block_kv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = (sk + pad) // block_kv
    kb = k.reshape(b, n_blocks, block_kv, k.shape[2], d)
    vb = v.reshape(b, n_blocks, block_kv, v.shape[2], v.shape[-1])

    if scale is None:
        scale = d ** -0.5
    qf = q.astype(jnp.float32)
    qpos = jnp.arange(sq) + q_offset

    def body(carry, xs):
        m, l, acc = carry
        blk_idx, kblk, vblk = xs
        kblk = _expand_kv(kblk, rep).astype(jnp.float32)
        vblk = _expand_kv(vblk, rep).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kblk) * scale
        s = _soft_cap(s, logit_softcap)
        kpos = blk_idx * block_kv + jnp.arange(block_kv)
        mask = kpos[None, :] < sk
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if sliding_window is not None:
            mask &= qpos[:, None] - kpos[None, :] < sliding_window
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vblk)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, h, sq, v.shape[-1]), jnp.float32)
    kb_t = jnp.moveaxis(kb, 1, 0)
    vb_t = jnp.moveaxis(vb, 1, 0)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (jnp.arange(n_blocks), kb_t, vb_t))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 1, 2).astype(q.dtype)   # [B, Sq, H, D]


def decode_attention(
    q: jax.Array,               # [B, H, D] — one new token per sequence
    k_cache: jax.Array,         # [B, S, Hkv, D]
    v_cache: jax.Array,         # [B, S, Hkv, D]
    cur_pos: jax.Array,         # [] or [B] — number of valid cache entries
    *,
    sliding_window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
) -> jax.Array:
    """Single-token attention against a (possibly seq-sharded) KV cache."""
    b, s, hkv, d = k_cache.shape
    h = q.shape[1]
    rep = h // hkv
    scale = d ** -0.5
    qf = q.astype(jnp.float32).reshape(b, hkv, rep, d)
    kf = k_cache.astype(jnp.float32)
    scores = jnp.einsum("bgrd,bsgd->bgrs", qf, kf) * scale
    scores = _soft_cap(scores, logit_softcap)
    kpos = jnp.arange(s)
    cur = jnp.asarray(cur_pos)
    cur_b = jnp.broadcast_to(cur.reshape(-1, *([1] * 0)), (b,)) \
        if cur.ndim <= 1 else cur
    valid = kpos[None, :] < cur_b[:, None]                  # [B, S]
    if sliding_window is not None:
        valid &= kpos[None, :] >= (cur_b[:, None] - sliding_window)
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------
def mlp_apply(params: dict, x: jax.Array, mlp_type: str) -> jax.Array:
    """Dense FFN. params: {'wi': [d, F] or [d, 2F] for gated, 'wo': [F, d]}."""
    dtype = x.dtype
    if mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if mlp_type == "swiglu" else \
            (lambda u: jax.nn.gelu(u, approximate=True))
        gu = x @ params["wi"]
        g, u = jnp.split(gu, 2, axis=-1)
        h = act(g.astype(jnp.float32)).astype(dtype) * u
    elif mlp_type == "relu2":
        h = x @ params["wi"]
        h = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(dtype)
    elif mlp_type == "gelu":
        h = x @ params["wi"]
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(dtype)
    else:
        raise ValueError(f"unknown mlp_type {mlp_type}")
    return h @ params["wo"]


def mlp_param_shapes(d_model: int, d_ff: int, mlp_type: str) -> dict:
    wi_cols = 2 * d_ff if mlp_type in ("swiglu", "geglu") else d_ff
    return {"wi": (d_model, wi_cols), "wo": (d_ff, d_model)}
