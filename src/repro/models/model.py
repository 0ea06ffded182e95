"""Unified model stack covering all assigned architecture families.

One implementation, six families: dense decoders, MoE decoders, SSM stacks
(Mamba2), hybrid interleaves (Jamba), VLM backbones (embedding-prefix stub)
and encoder-decoder (Whisper, conv-frontend stub).

Layers are organized as ``n_periods`` repetitions of a *block pattern*
(``cfg.block_pattern``); parameters are stacked over periods so the whole
stack is a single ``lax.scan`` — this keeps HLO size (and therefore
dry-run compile time) independent of depth.  Uniform models have a
pattern of length 1; Jamba has length 8.

Public entry points:
  param_shapes / init_params
  forward            — full-sequence forward (train / prefill), optional
                       routing-trace collection for the SliceMoE engine
  lm_loss            — chunked cross-entropy (never materializes [T, V]
                       logits for the full sequence at once)
  init_cache         — decode-state pytree (KV caches / SSM states)
  prefill            — forward + cache population
  decode_step        — single-token step against the cache
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import BlockSpec, ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

LOSS_CHUNKS = 16
# The leading dense layers (``cfg.first_k_dense``, params and cache under
# ``prefix``) run before the scanned pattern.
PREFIX_SPEC = BlockSpec("attn", "dense")


def _dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ==========================================================================
# Parameter shapes / init
# ==========================================================================
def _attn_shapes(cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    sh = {
        "wq": (d, h * hd),
        "wk": (d, kv * hd),
        "wv": (d, kv * hd),
        "wo": (h * hd, d),
        "norm": (d,),
    }
    if cfg.qkv_bias:
        sh["bq"] = (h * hd,)
        sh["bk"] = (kv * hd,)
        sh["bv"] = (kv * hd,)
    if cross:
        sh = {("c_" + k if k != "norm" else "c_norm"): v
              for k, v in sh.items()}
    return sh


def _mla_shapes(cfg: ModelConfig) -> dict:
    """Latent attention: the query projection (no query latent), the
    down-projection to the kv latent and the rope'd key part, the
    latent's norm, its up-projection to each head's [k_nope | v], and the
    output projection."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
        cfg.v_head_dim
    return {"wq": (d, h * (nope + rope)), "wkv_a": (d, r + rope),
            "kv_a_norm": (r,), "wkv_b": (r, h * (nope + vd)),
            "wo": (h * vd, d), "norm": (d,)}


def _block_shapes(cfg: ModelConfig, spec: BlockSpec, decoder: bool) -> dict:
    sh: dict = {}
    if spec.mixer == "attn" and cfg.attn_kind == "mla":
        sh.update(_mla_shapes(cfg))
    elif spec.mixer == "attn":
        sh.update(_attn_shapes(cfg))
        if decoder and cfg.is_encdec:
            sh.update(_attn_shapes(cfg, cross=True))
    else:
        assert cfg.ssm is not None
        sh["ssm"] = S.ssm_param_shapes(cfg.d_model, cfg.ssm)
        sh["ssm_norm"] = (cfg.d_model,)
    if spec.ffn == "dense":
        sh["mlp"] = L.mlp_param_shapes(cfg.d_model, cfg.d_ff, cfg.mlp_type)
        sh["mlp_norm"] = (cfg.d_model,)
    elif spec.ffn == "moe":
        moe_sh = M.moe_param_shapes(cfg.d_model, cfg.moe)
        if cfg.quantized_serve:
            moe_sh["experts"] = M.quantized_expert_shapes(
                cfg.d_model, cfg.moe)
        sh["moe"] = moe_sh
        sh["moe_norm"] = (cfg.d_model,)
    return sh


def param_shapes(cfg: ModelConfig) -> dict:
    """Nested dict of shape-tuples mirroring the param pytree."""
    def stack(shapes: dict, n: int) -> dict:
        return jax.tree_util.tree_map(
            lambda s: (n,) + s, shapes,
            is_leaf=lambda x: isinstance(x, tuple) and
            all(isinstance(i, int) for i in x))

    blocks = {
        f"pos{i}": stack(_block_shapes(cfg, spec, decoder=True),
                         cfg.n_periods)
        for i, spec in enumerate(cfg.block_pattern)
    }
    if cfg.first_k_dense:
        blocks["prefix"] = stack(
            _block_shapes(cfg, PREFIX_SPEC, decoder=True), cfg.first_k_dense)
    v_embed = cfg.padded_vocab if cfg.tie_embeddings else cfg.vocab_size
    sh = {
        "embed": (v_embed, cfg.d_model),
        "blocks": blocks,
        "final_norm": (cfg.d_model,),
    }
    if not cfg.tie_embeddings:
        sh["unembed"] = (cfg.d_model, cfg.padded_vocab)
    if cfg.is_encdec:
        enc_block = _block_shapes(
            cfg, BlockSpec("attn", "dense"), decoder=False)
        sh["encoder"] = {
            "blocks": stack(enc_block, cfg.encoder_layers),
            "final_norm": (cfg.d_model,),
        }
    return sh


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    if cfg.quantized_serve:
        # init the float model, then convert experts to AMAT form
        from repro.core.amat import MatConfig
        from repro.models.moe import quantize_params_for_serve

        base = dataclasses.replace(cfg, quantized_serve=False)
        return quantize_params_for_serve(
            init_params(base, key), cfg, MatConfig(8, 4))
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and
        all(isinstance(i, int) for i in x))
    keys = jax.random.split(key, len(leaves))
    dtype = _dt(cfg)

    def init_one(shape, k):
        if len(shape) == 1 or shape[-1] == 1:
            return jnp.zeros(shape, dtype)
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        return (jax.random.normal(k, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    inited = [init_one(s, k) for s, k in zip(leaves, keys)]
    params = jax.tree_util.tree_unflatten(treedef, inited)

    # Non-matrix special inits.
    def fix_blocks(bp: dict):
        for name, blk in bp.items():
            if "ssm" in blk:
                n = blk["ssm"]["A_log"].shape
                blk["ssm"]["A_log"] = jnp.log(
                    jnp.linspace(1.0, 16.0, n[-1], dtype=jnp.float32)
                    * jnp.ones(n, jnp.float32))
                blk["ssm"]["D"] = jnp.ones(n, jnp.float32)
                blk["ssm"]["dt_bias"] = jnp.full(n, -2.0, jnp.float32)
                blk["ssm"]["conv_w"] = (jax.random.normal(
                    jax.random.fold_in(key, hash(name) % 2**31),
                    blk["ssm"]["conv_w"].shape, jnp.float32) * 0.2
                ).astype(dtype)
    fix_blocks(params["blocks"])
    return params


# ==========================================================================
# Blocks
# ==========================================================================
def _attn_qkv(p: dict, x: jax.Array, cfg: ModelConfig, prefix: str = ""):
    b, s, _ = x.shape
    q = x @ p[prefix + "wq"]
    k = x @ p[prefix + "wk"]
    v = x @ p[prefix + "wv"]
    if cfg.qkv_bias:
        q = q + p[prefix + "bq"].astype(q.dtype)
        k = k + p[prefix + "bk"].astype(k.dtype)
        v = v + p[prefix + "bv"].astype(v.dtype)
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def _rope_freqs(cfg: ModelConfig):
    """(inverse frequencies, cos/sin factor) of latent attention's rope
    dims: YaRN where the config scales rope, else plain."""
    dim = cfg.qk_rope_head_dim
    if cfg.rope_scaling is None:
        return L.rope_frequencies(dim, cfg.rope_theta), 1.0
    return (L.yarn_inv_freq(dim, cfg.rope_theta, cfg.rope_scaling),
            L.yarn_attention_factor(cfg.rope_scaling))


def _mla_scale(cfg: ModelConfig) -> float:
    return L.mla_softmax_scale(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
                               cfg.rope_scaling)


def _mla_qkv(p: dict, h: jax.Array, cfg: ModelConfig, positions):
    """Latent attention's projections of the normed input h [B, S, d]:
    the queries [B, S, H, nope + rope] with the rope part rotated, and
    the cache rows [B, S, rank + rope]: the normed latent ``c`` and the
    rotated key part ``k_pe`` that every head shares."""
    b, s, _ = h.shape
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    inv, fac = _rope_freqs(cfg)
    q = (h @ p["wq"]).reshape(b, s, cfg.n_heads, -1)
    a = h @ p["wkv_a"]
    c = L.rms_norm(a[..., :r], p["kv_a_norm"], cfg.norm_eps)
    k_pe = L.apply_rope_interleaved(a[..., None, r:], positions, inv, fac)
    q_pe = L.apply_rope_interleaved(q[..., nope:], positions, inv, fac)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    return q, jnp.concatenate([c, k_pe[..., 0, :]], axis=-1)


def _mla_expand(p: dict, ckv: jax.Array, cfg: ModelConfig):
    """Keys [B, S, H, nope + rope] and values [B, S, H, v] of latent rows
    [B, S, >= rank + rope]: each head's [k_nope | v] up-projected from the
    latent, and the one rope'd key part broadcast to every head."""
    b, s, _ = ckv.shape
    r, nope, h = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.n_heads
    rope = cfg.qk_rope_head_dim
    kv = (ckv[..., :r] @ p["wkv_b"]).reshape(b, s, h, -1)
    k_pe = jnp.broadcast_to(ckv[..., None, r:r + rope], (b, s, h, rope))
    return jnp.concatenate([kv[..., :nope], k_pe], axis=-1), kv[..., nope:]


def _pad_last(x: jax.Array, width: int) -> jax.Array:
    """Zero-pad the last axis of ``x`` to ``width``."""
    pad = [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])]
    return jnp.pad(x, pad)


def _mla_attn_block(p: dict, x: jax.Array, cfg: ModelConfig, *,
                    positions: jax.Array):
    """Causal latent attention over a whole sequence, in the expanded
    form (keys and values up-projected).  Returns the new residual and
    the sequence's cache rows."""
    with jax.named_scope("mla.prefill"):
        h = L.rms_norm(x, p["norm"], cfg.norm_eps)
        q, ckv = _mla_qkv(p, h, cfg, positions)
        k, v = _mla_expand(p, ckv, cfg)
        o = L.attention(q, k, v, causal=True, scale=_mla_scale(cfg))
        o = o.reshape(x.shape[0], x.shape[1], -1) @ p["wo"]
        return x + o, ckv


def _mla_decode_block(p: dict, x: jax.Array, cfg: ModelConfig,
                      ckv_cache: jax.Array, *, pos, positions, vector_pos):
    """One token per sequence of latent attention in the absorbed form:
    the key up-projection is folded into the query (``q_lat``), the
    latent cache is attended as multi-query attention by
    ``mla_decode_attention``, and the value up-projection is applied to
    its output.  Returns the new residual and cache."""
    from repro.kernels.mla_decode import mla_decode_attention

    with jax.named_scope("mla.decode"):
        b = x.shape[0]
        r, nope, h = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.n_heads
        hn = L.rms_norm(x, p["norm"], cfg.norm_eps)
        q, row = _mla_qkv(p, hn, cfg, positions)
        row = _pad_last(row, ckv_cache.shape[-1])
        if vector_pos:
            cache = ckv_cache.at[jnp.arange(b), pos].set(
                row[:, 0].astype(ckv_cache.dtype))
        else:
            cache = jax.lax.dynamic_update_slice(
                ckv_cache, row.astype(ckv_cache.dtype), (0, pos, 0))
        w = p["wkv_b"].reshape(r, h, -1).astype(jnp.float32)
        q = q[:, 0].astype(jnp.float32)
        q_lat = jnp.einsum("bhn,rhn->bhr", q[..., :nope], w[..., :nope])
        lengths = jnp.broadcast_to(pos + 1, (b,))
        o_lat = mla_decode_attention(
            jnp.concatenate([q_lat, q[..., nope:]], axis=-1), cache,
            lengths, scale=_mla_scale(cfg), rank=r)
        o = jnp.einsum("bhr,rhv->bhv", o_lat, w[..., nope:]).astype(x.dtype)
        return x + (o.reshape(b, -1) @ p["wo"])[:, None, :], cache


def _self_attn_block(p: dict, x: jax.Array, cfg: ModelConfig, *,
                     causal: bool, positions: jax.Array,
                     window: Optional[int]):
    """Self-attention with its norm and residual.  Returns the new
    residual and what the cache keeps: ``(k, v)``, or the latent rows
    under latent attention."""
    if cfg.attn_kind == "mla":
        return _mla_attn_block(p, x, cfg, positions=positions)
    with jax.named_scope("attn"):
        h = L.rms_norm(x, p["norm"], cfg.norm_eps)
        q, k, v = _attn_qkv(p, h, cfg)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        o = L.attention(q, k, v, causal=causal, sliding_window=window,
                        logit_softcap=cfg.logit_softcap)
        o = o.reshape(x.shape[0], x.shape[1], -1) @ p["wo"]
        return x + o, (k, v)


def _cross_attn_block(p: dict, x: jax.Array, enc_k: jax.Array,
                      enc_v: jax.Array, cfg: ModelConfig):
    h = L.rms_norm(x, p["c_norm"], cfg.norm_eps)
    b, s, _ = h.shape
    q = (h @ p["c_wq"]).reshape(b, s, cfg.n_heads, cfg.head_dim)
    o = L.attention(q, enc_k, enc_v, causal=False,
                    logit_softcap=cfg.logit_softcap)
    o = o.reshape(b, s, -1) @ p["c_wo"]
    return x + o


def _ffn_block(p: dict, x: jax.Array, cfg: ModelConfig, spec: BlockSpec, *,
               collect, use_lsb=None, gate_override=None,
               policy=None, policy_state=None, mat=None, token_mask=None,
               quant_execution=None, force_high_bit=False, layer=None):
    aux = None
    if spec.ffn == "dense":
        h = L.rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        x = x + L.mlp_apply(p["mlp"], h, cfg.mlp_type)
    elif spec.ffn == "moe":
        with jax.named_scope("moe"):
            h = L.rms_norm(x, p["moe_norm"], cfg.norm_eps)
            b, s, d = h.shape
            y, aux = M.moe_apply(
                p["moe"], h.reshape(-1, d), cfg.moe,
                use_lsb=use_lsb, gate_override=gate_override,
                policy=policy, policy_state=policy_state, mat=mat,
                token_mask=token_mask, quant_execution=quant_execution,
                force_high_bit=force_high_bit, layer=layer)
            x = x + y.reshape(b, s, d)
            if not collect:
                aux = {"aux_loss": aux["aux_loss"],
                       "dropped_frac": aux["dropped_frac"]}
    return x, aux


def _ssm_block(p: dict, x: jax.Array, cfg: ModelConfig):
    h = L.rms_norm(x, p["ssm_norm"], cfg.norm_eps)
    y = S.ssm_forward(p["ssm"], h, cfg.ssm)
    return x + y


def _scan_periods(body, carry, blocks: dict, cfg: ModelConfig, xs=None):
    """``lax.scan`` of ``body(carry, period_params, layer, xs_p)`` over
    the model's periods; ``layer`` is the period index.

    Quantized expert tensors (``experts/{wi_q,wo_q}`` or the flat
    ``wi_codes/...`` form) stay out of the scanned inputs: each period
    sees them whole (``[P, E, K, N]``) and its MoE reads period ``layer``
    (see :func:`repro.models.moe.moe_apply`).  Scanned, they would reach
    the body as per-period slices, and the expert kernel, a custom call,
    cannot read a slice in place: XLA would copy every layer's codes out
    of the params on every step.
    """
    held = {}
    scanned = {}
    for key, blk in blocks.items():
        if key == "prefix":
            continue
        experts = blk.get("moe", {}).get("experts", {})
        if "wi_q" in experts or "wi_codes" in experts:
            held[key] = experts
            blk = {**blk, "moe": {**blk["moe"], "experts": {}}}
        scanned[key] = blk

    def step(c, inputs):
        layer, period_params, xs_p = inputs
        period_params = {
            k: {**p, "moe": {**p["moe"], "experts": held[k]}}
            if k in held else p for k, p in period_params.items()}
        return body(c, period_params, layer, xs_p)

    return jax.lax.scan(step, carry,
                        (jnp.arange(cfg.n_periods), scanned, xs))


def _prefix_layers(params: dict, cfg: ModelConfig) -> list:
    """Each leading dense layer's params, in order."""
    if not cfg.first_k_dense:
        return []
    pre = params["blocks"]["prefix"]
    return [jax.tree_util.tree_map(lambda a, i=i: a[i], pre)
            for i in range(cfg.first_k_dense)]


def _stack_entries(entries: list) -> dict:
    """Per-layer cache entries stacked over a leading layer axis."""
    return {name: jnp.stack([e[name] for e in entries])
            for name in entries[0]}


# ==========================================================================
# Encoder (whisper)
# ==========================================================================
def _encode(params: dict, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """frames: [B, enc_seq, d_model] — precomputed frontend embeddings."""
    enc = params["encoder"]
    positions = jnp.arange(frames.shape[1])[None, :]

    def body(x, p):
        x, _ = _self_attn_block(p, x, cfg, causal=False,
                                positions=positions, window=None)
        x, _ = _ffn_block(p, x, cfg, BlockSpec("attn", "dense"),
                          collect=False)
        return x, None

    x, _ = jax.lax.scan(body, frames.astype(_dt(cfg)), enc["blocks"])
    return L.rms_norm(x, enc["final_norm"], cfg.norm_eps)


def _enc_kv(p: dict, enc_out: jax.Array, cfg: ModelConfig):
    b, s, _ = enc_out.shape
    k = (enc_out @ p["c_wk"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = (enc_out @ p["c_wv"]).reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    return k, v


# ==========================================================================
# Full-sequence forward
# ==========================================================================
def embed_inputs(params: dict, cfg: ModelConfig, tokens: jax.Array,
                 prefix_embeds: Optional[jax.Array]) -> jax.Array:
    from repro.launch.sharding import shard_hint

    if cfg.onehot_embed:
        # One-hot matmul lookup: GSPMD partitions a dot over the vocab-
        # sharded table cleanly (plain all-reduce over vocab shards),
        # where a gather triggers involuntary full rematerialization
        # (replicate-then-reshard).  The one-hot fuses into the dot on
        # TPU (iota-compare, never materialized at [T, V]).
        oh = jax.nn.one_hot(tokens, params["embed"].shape[0], dtype=_dt(cfg))
        x = oh @ params["embed"].astype(_dt(cfg))
    else:
        x = params["embed"][tokens].astype(_dt(cfg))
    if cfg.prefix_len and prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(_dt(cfg)), x], axis=1)
    return shard_hint(x, ("pod", "data"), None, None)


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,                       # [B, S_text]
    *,
    prefix_embeds: Optional[jax.Array] = None,   # [B, prefix_len, d]
    encoder_frames: Optional[jax.Array] = None,  # [B, enc_seq, d]
    collect_trace: bool = False,
    use_window: bool = False,
    mat=None,
    quant_execution: Optional[bool] = None,
):
    """Returns (hidden [B, S, d], aux dict with moe traces / losses)."""
    x = embed_inputs(params, cfg, tokens, prefix_embeds)
    b, s, d = x.shape
    positions = jnp.arange(s)[None, :]
    window = cfg.sliding_window if (use_window or cfg.always_swa) else None

    enc_out = None
    if cfg.is_encdec:
        assert encoder_frames is not None
        enc_out = _encode(params, cfg, encoder_frames)

    pattern = cfg.block_pattern
    for p in _prefix_layers(params, cfg):
        x, _ = _self_attn_block(p, x, cfg, causal=True, positions=positions,
                                window=window)
        x, _ = _ffn_block(p, x, cfg, PREFIX_SPEC, collect=False)

    def period_body(x, period_params, layer, _):
        if cfg.seq_parallel:
            # Megatron-style sequence parallelism: the residual stream is
            # seq-sharded over the model axis between blocks, turning the
            # per-block all-reduce into reduce-scatter + all-gather and
            # cutting resident activation memory by the model-axis size.
            from repro.launch.sharding import shard_hint
            x = shard_hint(x, ("pod", "data"), "model", None)
        auxes = []
        for i, spec in enumerate(pattern):
            p = period_params[f"pos{i}"]
            if spec.mixer == "attn":
                x, _ = _self_attn_block(
                    p, x, cfg, causal=True, positions=positions,
                    window=window)
                if cfg.is_encdec:
                    ek, ev = _enc_kv(p, enc_out, cfg)
                    x = _cross_attn_block(p, x, ek, ev, cfg)
            else:
                x = _ssm_block(p, x, cfg)
            x, aux = _ffn_block(p, x, cfg, spec, collect=collect_trace,
                                mat=mat, quant_execution=quant_execution,
                                layer=layer)
            if aux is not None:
                auxes.append(aux)
        if auxes:
            stacked = {k: jnp.stack([a[k] for a in auxes])
                       for k in auxes[0]}
        else:
            stacked = {}
        return x, stacked

    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        body = jax.checkpoint(period_body, prevent_cse=False, policy=policy)
    else:
        body = jax.checkpoint(period_body, prevent_cse=False)
    x, aux_stacked = _scan_periods(body, x, params["blocks"], cfg)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    aux = {}
    if aux_stacked:
        aux["moe"] = aux_stacked                  # leaves [n_periods, n_moe_pos, ...]
        aux["aux_loss"] = jnp.sum(aux_stacked["aux_loss"])
    else:
        aux["aux_loss"] = jnp.zeros((), jnp.float32)
    return x, aux


def unembed(params: dict, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    with jax.named_scope("unembed"):
        w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = (h @ w.astype(h.dtype)).astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:
            # mask pad columns so softmax / argmax / logsumexp ignore them
            col = jnp.arange(cfg.padded_vocab)
            logits = jnp.where(col < cfg.vocab_size, logits, -1e30)
        return logits


def lm_loss(params: dict, cfg: ModelConfig, tokens: jax.Array,
            labels: jax.Array, *, prefix_embeds=None, encoder_frames=None,
            aux_weight: float = 0.01):
    """Chunked cross-entropy over the flattened token stream."""
    h, aux = forward(params, cfg, tokens, prefix_embeds=prefix_embeds,
                     encoder_frames=encoder_frames)
    b, s, d = h.shape
    if cfg.prefix_len and prefix_embeds is not None:
        h = h[:, cfg.prefix_len:]
        s = h.shape[1]
    hf = h.reshape(-1, d)
    lf = labels.reshape(-1)
    T = hf.shape[0]
    n_chunks = LOSS_CHUNKS if T % LOSS_CHUNKS == 0 else 1
    hc = hf.reshape(n_chunks, T // n_chunks, d)
    lc = lf.reshape(n_chunks, T // n_chunks)

    def chunk_loss(carry, xs):
        hx, lx = xs
        logits = unembed(params, cfg, hx)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lx[:, None], axis=-1)[:, 0]
        return carry + jnp.sum(logz - gold), None

    total, _ = jax.lax.scan(chunk_loss, jnp.zeros((), jnp.float32), (hc, lc))
    loss = total / T
    return loss + aux_weight * aux["aux_loss"], aux


# ==========================================================================
# Decode cache
# ==========================================================================
@dataclasses.dataclass(frozen=True)
class CacheDims:
    batch: int
    max_seq: int


def _quant_kv(x: jax.Array):
    """Per-(token, head) dynamic int8 quantization of K/V rows.

    x: [..., hd] -> (codes int8 [..., hd], scales f32 [...]).
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale


def _dequant_kv(codes: jax.Array, scale: jax.Array, dtype):
    return (codes.astype(jnp.float32) * scale[..., None]).astype(dtype)


def cache_rows(cfg: ModelConfig, max_seq: int) -> int:
    """Rows of a layer's KV cache for ``max_seq`` positions: a latent
    cache holds whole blocks of the decode kernel."""
    if cfg.attn_kind == "mla":
        from repro.kernels.mla_decode import mla_cache_rows
        return mla_cache_rows(max_seq)
    return max_seq


def mla_width(cfg: ModelConfig) -> int:
    """Values a latent cache row is stored in: the latent and the rope'd
    key part, padded to whole lanes."""
    from repro.kernels.mla_decode import mla_cache_width
    return mla_cache_width(cfg.kv_lora_rank + cfg.qk_rope_head_dim)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=None) -> dict:
    """Decode-state pytree, stacked over periods per pattern position
    (and over the leading dense layers under ``prefix``).  A latent
    attention layer's entry is ``ckv`` [.., B, rows, width]: the normed
    latent and the rope'd key part of every position (``rank + rope``
    values, zero-padded to ``mla_width``)."""
    dtype = dtype or _dt(cfg)
    np_ = cfg.n_periods
    int8_kv = cfg.kv_dtype == "int8"
    cache: dict = {"pos": jnp.zeros((), jnp.int32)}
    layers = [(f"pos{i}", spec, np_)
              for i, spec in enumerate(cfg.block_pattern)]
    if cfg.first_k_dense:
        layers.append(("prefix", PREFIX_SPEC, cfg.first_k_dense))
    for key, spec, np_ in layers:
        if spec.mixer == "attn" and cfg.attn_kind == "mla":
            cache[key] = {"ckv": jnp.zeros(
                (np_, batch, cache_rows(cfg, max_seq), mla_width(cfg)),
                dtype)}
        elif spec.mixer == "attn":
            kv_shape = (np_, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
            kv_dt = jnp.int8 if int8_kv else dtype
            entry = {"k": jnp.zeros(kv_shape, kv_dt),
                     "v": jnp.zeros(kv_shape, kv_dt)}
            if int8_kv:
                sc_shape = kv_shape[:-1]
                entry["k_scale"] = jnp.zeros(sc_shape, jnp.float32)
                entry["v_scale"] = jnp.zeros(sc_shape, jnp.float32)
            if cfg.is_encdec:
                cs = (np_, batch, cfg.encoder_seq, cfg.n_kv_heads,
                      cfg.head_dim)
                entry["ck"] = jnp.zeros(cs, dtype)
                entry["cv"] = jnp.zeros(cs, dtype)
            cache[key] = entry
        else:
            ssm = cfg.ssm
            di = ssm.d_inner(cfg.d_model)
            h = ssm.n_heads(cfg.d_model)
            cache[key] = {
                "state": jnp.zeros((np_, batch, h, ssm.head_dim,
                                    ssm.d_state), jnp.float32),
                "conv": jnp.zeros((np_, batch, ssm.d_conv - 1,
                                   ssm.conv_channels(cfg.d_model)), dtype),
            }
    return cache


# ==========================================================================
# Prefill
# ==========================================================================
def prefill(params: dict, cfg: ModelConfig, tokens: jax.Array,
            max_seq: int, *, prefix_embeds=None, encoder_frames=None,
            collect_trace: bool = False, use_window: bool = False,
            mat=None, quant_execution: Optional[bool] = None,
            policy=None):
    """Forward over the prompt, returning (last-token logits, cache, aux).

    ``policy``: optional *state-free* RoutingPolicy (e.g. cumsum) to
    route the prompt with — selection and the aux trace (ids/gates/
    active/critical) follow the policy, while compute stays high-bit for
    every routed expert (the engine's prefill discipline).  Stateful
    kinds needing residency masks cannot run here.
    """
    x = embed_inputs(params, cfg, tokens, prefix_embeds)
    b, s, d = x.shape
    positions = jnp.arange(s)[None, :]
    window = cfg.sliding_window if (use_window or cfg.always_swa) else None
    dtype = _dt(cfg)

    enc_out = None
    if cfg.is_encdec:
        assert encoder_frames is not None
        enc_out = _encode(params, cfg, encoder_frames)

    pattern = cfg.block_pattern

    def attn_entry(p, x):
        """Self-attention (and cross-attention) over the prompt, and the
        layer's cache entry padded to the cache's rows."""
        x, kv = _self_attn_block(p, x, cfg, causal=True, positions=positions,
                                 window=window)
        if cfg.attn_kind == "mla":
            rows = jnp.pad(kv, ((0, 0), (0, cache_rows(cfg, max_seq) - s),
                                (0, 0)))
            return x, {"ckv": _pad_last(rows, mla_width(cfg)).astype(dtype)}
        k, v = kv
        pad = max_seq - s
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if cfg.kv_dtype == "int8":
            kq, ks = _quant_kv(kp)
            vq, vs = _quant_kv(vp)
            entry = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        else:
            entry = {"k": kp.astype(dtype), "v": vp.astype(dtype)}
        if cfg.is_encdec:
            ek, ev = _enc_kv(p, enc_out, cfg)
            x = _cross_attn_block(p, x, ek, ev, cfg)
            entry["ck"] = ek.astype(dtype)
            entry["cv"] = ev.astype(dtype)
        return x, entry

    prefix_entries = []
    for p in _prefix_layers(params, cfg):
        x, entry = attn_entry(p, x)
        x, _ = _ffn_block(p, x, cfg, PREFIX_SPEC, collect=False)
        prefix_entries.append(entry)

    def period_body(x, period_params, layer, _):
        cache_entries = {}
        auxes = []
        for i, spec in enumerate(pattern):
            p = period_params[f"pos{i}"]
            key = f"pos{i}"
            if spec.mixer == "attn":
                x, cache_entries[key] = attn_entry(p, x)
            else:
                h = L.rms_norm(x, p["ssm_norm"], cfg.norm_eps)
                y, (state, conv_tail) = S.ssm_forward(
                    p["ssm"], h, cfg.ssm, return_state=True)
                x = x + y
                cache_entries[key] = {"state": state,
                                      "conv": conv_tail.astype(dtype)}
            x, aux = _ffn_block(p, x, cfg, spec, collect=collect_trace,
                                mat=mat, quant_execution=quant_execution,
                                policy=policy,
                                force_high_bit=policy is not None,
                                layer=layer)
            if aux is not None:
                auxes.append(aux)
        stacked = {}
        if auxes:
            stacked = {k: jnp.stack([a[k] for a in auxes])
                       for k in auxes[0]}
        return x, (cache_entries, stacked)

    x, (cache_stacked, aux_stacked) = _scan_periods(
        period_body, x, params["blocks"], cfg)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, cfg, x[:, -1])

    cache = dict(cache_stacked)
    if prefix_entries:
        cache["prefix"] = _stack_entries(prefix_entries)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    aux = {"moe": aux_stacked} if aux_stacked else {}
    return logits, cache, aux


# ==========================================================================
# Decode step
# ==========================================================================
def decode_step(params: dict, cfg: ModelConfig, token: jax.Array,
                cache: dict, *, encoder_frames=None,
                collect_trace: bool = False,
                use_lsb: Optional[dict] = None,
                gate_override: Optional[dict] = None,
                policy=None,
                policy_state: Optional[dict] = None,
                alpha=None,
                mat=None,
                token_mask: Optional[jax.Array] = None,
                use_window: bool = False,
                quant_execution: Optional[bool] = None):
    """One decode step.  token: [B] int32.  Returns (logits, cache, aux).

    ``use_lsb`` / ``gate_override`` / ``policy_state`` are optional
    per-(position, period) overrides injected by the SliceMoE engine:
      use_lsb[f"pos{i}"]        : [n_periods, E] bool
      gate_override[f"pos{i}"]  : ([n_periods, B, k] gates, ids)
      policy_state[f"pos{i}"]   : {'cached_msb'/'cached_lsb': [n_periods, E]}
    ``policy`` is a static RoutingPolicy; ``alpha`` a dynamic scalar
    (Cache-Prior boost) broadcast to every MoE layer; ``mat`` the AMAT
    MatConfig when expert weights are quantized.  ``token_mask`` ([B]
    bool) excludes padding rows from MoE routing/capacity (see
    :func:`repro.models.moe.moe_apply`).

    ``cache["pos"]`` may be a scalar (all sequences aligned — the original
    single-request path) or a ``[B]`` vector of per-sequence lengths (the
    continuous-batching path, where each slot was prefilled at a different
    time).  With vector positions every sequence writes its KV row at its
    own offset and attends over its own valid prefix.
    """
    b = token.shape[0]
    pos = cache["pos"]
    vector_pos = getattr(pos, "ndim", 0) == 1      # per-sequence positions
    x = params["embed"][token].astype(_dt(cfg))[:, None, :]   # [B, 1, d]
    if vector_pos:
        positions = pos[:, None].astype(jnp.int32)            # [B, 1]
    else:
        positions = jnp.full((1, 1), pos, jnp.int32)
    window = cfg.sliding_window if (use_window or cfg.always_swa) else None
    pattern = cfg.block_pattern

    def attn_step(p, x, entry_in):
        """Self-attention (and cross-attention) of the new token against
        one layer's cache entry; returns the new residual and entry."""
        if cfg.attn_kind == "mla":
            x, ckv = _mla_decode_block(p, x, cfg, entry_in["ckv"], pos=pos,
                                       positions=positions,
                                       vector_pos=vector_pos)
            return x, {"ckv": ckv}
        with jax.named_scope("attn"):
            h = L.rms_norm(x, p["norm"], cfg.norm_eps)
            q, k, v = _attn_qkv(p, h, cfg)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
            S_alloc = entry_in["k"].shape[1]
            ring = cfg.ring_kv
            pos_w = (pos % S_alloc) if ring else pos

            def write_row(buf, val):
                # val: [B, 1, ...] — the new token's row per sequence.
                if vector_pos:
                    return buf.at[jnp.arange(b), pos_w].set(
                        val[:, 0].astype(buf.dtype))
                start = (0, pos_w) + (0,) * (buf.ndim - 2)
                return jax.lax.dynamic_update_slice(
                    buf, val.astype(buf.dtype), start)

            if cfg.kv_dtype == "int8":
                kq, ks = _quant_kv(k)
                vq, vs = _quant_kv(v)
                kc = write_row(entry_in["k"], kq)
                vc = write_row(entry_in["v"], vq)
                ksc = write_row(entry_in["k_scale"], ks)
                vsc = write_row(entry_in["v_scale"], vs)
                entry = {"k": kc, "v": vc, "k_scale": ksc,
                         "v_scale": vsc}
            else:
                kc = write_row(entry_in["k"], k)
                vc = write_row(entry_in["v"], v)
                ksc = vsc = None
                entry = {"k": kc, "v": vc}

            # Sliding-window decode reads only the last `window` cache
            # rows (true O(window) traffic, not a masked full read).
            S_cache = kc.shape[1]
            if vector_pos:
                # Per-sequence lengths: rows diverge, so the compact
                # dynamic-slice read doesn't apply — read the full
                # cache and let the per-row mask in decode_attention
                # bound each sequence's valid prefix (and window).
                k_r, v_r = kc, vc
                ks_r, vs_r = ksc, vsc
                cur = jnp.minimum(pos + 1, S_cache) if ring else pos + 1
                win_mask = None if ring else window
            elif ring:
                # ring buffer: every resident row is within the window;
                # attention is permutation-invariant so wraparound
                # order doesn't matter.
                k_r, v_r = kc, vc
                ks_r, vs_r = ksc, vsc
                cur = jnp.minimum(pos + 1, S_cache)
                win_mask = None
            elif window is not None and S_cache > window:
                start = jnp.clip(pos + 1 - window, 0, S_cache - window)
                k_r = jax.lax.dynamic_slice_in_dim(kc, start, window, 1)
                v_r = jax.lax.dynamic_slice_in_dim(vc, start, window, 1)
                if ksc is not None:
                    ks_r = jax.lax.dynamic_slice_in_dim(ksc, start,
                                                        window, 1)
                    vs_r = jax.lax.dynamic_slice_in_dim(vsc, start,
                                                        window, 1)
                cur = pos + 1 - start
                win_mask = None
            else:
                k_r, v_r = kc, vc
                ks_r, vs_r = ksc, vsc
                cur = pos + 1
                win_mask = window
            if cfg.kv_dtype == "int8":
                k_f = _dequant_kv(k_r, ks_r, _dt(cfg))
                v_f = _dequant_kv(v_r, vs_r, _dt(cfg))
            else:
                k_f, v_f = k_r, v_r
            o = L.decode_attention(
                q[:, 0], k_f, v_f, cur, sliding_window=win_mask,
                logit_softcap=cfg.logit_softcap)
            x = x + (o.reshape(b, -1) @ p["wo"])[:, None, :]
            if cfg.is_encdec:
                x = _cross_attn_block(
                    p, x, entry_in["ck"], entry_in["cv"], cfg)
                entry["ck"] = entry_in["ck"]
                entry["cv"] = entry_in["cv"]
            return x, entry

    prefix_entries = []
    for i, p in enumerate(_prefix_layers(params, cfg)):
        x, entry = attn_step(p, x, {name: leaf[i] for name, leaf
                                    in cache["prefix"].items()})
        x, _ = _ffn_block(p, x, cfg, PREFIX_SPEC, collect=False)
        prefix_entries.append(entry)

    def period_body(x, period_params, layer, xs):
        cache_in, overrides = xs
        cache_out = {}
        auxes = []
        for i, spec in enumerate(pattern):
            key = f"pos{i}"
            p = period_params[key]
            if spec.mixer == "attn":
                x, cache_out[key] = attn_step(p, x, cache_in[key])
            else:
                h = L.rms_norm(x, p["ssm_norm"], cfg.norm_eps)
                y, st, cb = S.ssm_decode_step(
                    p["ssm"], h[:, 0], cache_in[key]["state"],
                    cache_in[key]["conv"], cfg.ssm)
                x = x + y[:, None, :]
                cache_out[key] = {"state": st, "conv": cb}

            ul = overrides.get("use_lsb", {}).get(key) \
                if overrides else None
            go = overrides.get("gate", {}).get(key) if overrides else None
            ps = overrides.get("policy_state", {}).get(key) \
                if overrides else None
            if ps is not None and alpha is not None:
                ps = dict(ps)
                ps["alpha"] = alpha
            x, aux = _ffn_block(p, x, cfg, spec, collect=collect_trace,
                                use_lsb=ul, gate_override=go,
                                policy=policy, policy_state=ps, mat=mat,
                                token_mask=token_mask,
                                quant_execution=quant_execution,
                                layer=layer)
            if aux is not None:
                auxes.append(aux)
        stacked = {}
        if auxes:
            stacked = {k: jnp.stack([a[k] for a in auxes])
                       for k in auxes[0]}
        return x, (cache_out, stacked)

    overrides = {}
    if use_lsb is not None:
        overrides["use_lsb"] = use_lsb
    if gate_override is not None:
        overrides["gate"] = gate_override
    if policy_state is not None:
        overrides["policy_state"] = policy_state

    layer_cache = {k: v for k, v in cache.items()
                   if k not in ("pos", "prefix")}
    x, (new_cache, aux_stacked) = _scan_periods(
        period_body, x, params["blocks"], cfg, (layer_cache, overrides))

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, cfg, x[:, 0])
    if prefix_entries:
        new_cache["prefix"] = _stack_entries(prefix_entries)
    new_cache["pos"] = pos + 1
    aux = {"moe": aux_stacked} if aux_stacked else {}
    return logits, new_cache, aux


# ==========================================================================
# Convenience
# ==========================================================================
def count_params(params: dict) -> int:
    return sum(int(np.prod(x.shape))
               for x in jax.tree_util.tree_leaves(params))
