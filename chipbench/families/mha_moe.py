"""Decoder family ``mha_moe``: rotary multi-head attention (grouped KV
heads allowed, optional q/k/v biases) and the MoE block in every layer.

The program serves it as one repeating attention + MoE block
(``ModelConfig`` with a pattern of length one), its parameters stacked
over layers under ``blocks/pos0``.  Its router always renormalises the
top-k gates, a departure that each configuration of this family lists.
A configuration with leading dense layers is refused: the program's
layer patterns only repeat.

What a family module gives the harness (``chipbench.cellconfig.family``
loads it by file name):

* ``dims(conf, common)``: the common sizes plus the family's own;
* ``model_config(conf, dm)``: the program's ``ModelConfig``;
* ``param_shapes(dm)``: the weight tree's shapes in the program's layout;
* ``forward(blocks, x, plan, n_valid, lo_at, *, dm, lowp, lo_rows)``:
  the reference's layers over a padded sequence;
* ``dense_work(dm, kv_len)``: a decode step's work outside the routed
  experts.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.reference import HI, mm, moe_block, rms
from chipbench.work import BF16


# ------------------------------------------------------------------ sizes
def attention_dims(conf: dict) -> dict:
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    return {
        "heads": heads,
        "kv_heads": conf.get("num_key_value_heads", heads),
        "head_dim": conf.get("head_dim", d // heads),
        "rope_theta": float(conf["rope_theta"]),
        "qkv_bias": bool(conf.get("qkv_bias", conf.get("attention_bias",
                                                       False))),
    }


def dims(conf: dict, common: dict) -> dict:
    if conf.get("first_k_dense_replace", 0):
        raise ValueError("a leading dense layer cannot be served: the "
                         "program's layer patterns only repeat")
    # The served router renormalises whatever ``norm_topk_prob`` says.
    return dict(common, **attention_dims(conf), norm_topk=True)


def model_config(conf: dict, dm: dict):
    from repro.configs.base import ModelConfig
    from repro.models.moe import MoECfg

    eng = conf["engine"]
    return ModelConfig(
        name=conf["name"], arch_type="moe", n_layers=dm["layers"],
        d_model=dm["d"], n_heads=dm["heads"], n_kv_heads=dm["kv_heads"],
        head_dim=dm["head_dim"], d_ff=dm["dense_ff"], vocab_size=dm["vocab"],
        mlp_type="swiglu",
        moe=MoECfg(n_experts=dm["experts"], top_k=dm["top_k"],
                   d_ff=dm["expert_ff"],
                   n_shared_experts=1 if dm["shared_ff"] else 0,
                   d_ff_shared=dm["shared_ff"],
                   capacity_factor=eng["capacity_factor"],
                   mlp_type="swiglu"),
        rope_theta=dm["rope_theta"], norm_eps=dm["eps"],
        tie_embeddings=False, qkv_bias=dm["qkv_bias"], dtype="bfloat16",
        source=conf["source"])


# ---------------------------------------------------------------- weights
def attention_shapes(dm: dict, n: int) -> dict:
    """Attention leaves and the norm before it, stacked over ``n``
    layers."""
    d = dm["d"]
    q = dm["heads"] * dm["head_dim"]
    kv = dm["kv_heads"] * dm["head_dim"]
    blk = {"wq": (n, d, q), "wk": (n, d, kv), "wv": (n, d, kv),
           "wo": (n, q, d), "norm": (n, d)}
    if dm["qkv_bias"]:
        blk.update(bq=(n, q), bk=(n, kv), bv=(n, kv))
    return blk


def param_shapes(dm: dict) -> dict:
    d, L = dm["d"], dm["layers"]
    blk = dict(attention_shapes(dm, L), moe_norm=(L, d),
               moe=weights.moe_shapes(dm, L))
    return {"embed": (dm["vocab"], d), "blocks": {"pos0": blk},
            "final_norm": (d,), "unembed": (d, dm["vocab"])}


# -------------------------------------------------------------- reference
def rope(x, pos, theta):
    """Rotary embedding, rotate-half, over the last axis of [S, H, hd]."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, p, n_valid, *, dm, lowp):
    """Causal attention of one layer over a padded sequence, with its
    norm and residual.  Returns the new residual stream and which
    positions are valid (before ``n_valid``)."""
    S = x.shape[0]
    H, KV, hd = dm["heads"], dm["kv_heads"], dm["head_dim"]
    f32 = jnp.float32
    pos = jnp.arange(S)
    h = rms(x, p["norm"], dm["eps"])
    q = mm(h, p["wq"].astype(f32), lowp)
    k = mm(h, p["wk"].astype(f32), lowp)
    v = mm(h, p["wv"].astype(f32), lowp)
    if dm["qkv_bias"]:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q.reshape(S, H, hd), pos, dm["rope_theta"])
    k = rope(k.reshape(S, KV, hd), pos, dm["rope_theta"])
    v = v.reshape(S, KV, hd)
    rep = H // KV
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    if lowp:
        q, k, v = (t.astype(jnp.float8_e4m3fn).astype(f32) for t in (q, k, v))
    valid = pos < n_valid

    def attend(qb_pos):
        qb, pb = qb_pos
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * hd ** -0.5
        mask = (pb[:, None] >= pos[None, :]) & valid[None, :]
        pr = jax.nn.softmax(jnp.where(mask[None], sc, -1e30), -1)
        if lowp:
            pr = pr.astype(jnp.float8_e4m3fn).astype(f32)
        return jnp.einsum("hqk,khd->qhd", pr, v, precision=HI)

    blk_q = math.gcd(S, 256)
    o = jax.lax.map(attend, (q.reshape(S // blk_q, blk_q, H, hd),
                             pos.reshape(S // blk_q, blk_q)))
    return x + mm(o.reshape(S, H * hd), p["wo"].astype(f32), lowp), valid


@partial(jax.jit, static_argnames=("dm", "lowp", "lo_rows"))
def moe_layer(x, blk, li, ids, keep, hi, boost, n_valid, lo_at, *, dm, lowp,
              lo_rows):
    """Layer ``li`` of the stacked ``blk``: attention, then the MoE block.
    Returns the new residual stream, and for each position the widest
    ``route_gap`` and ``msb_gate`` of its selections (0 on padding)."""
    dm = dict(dm)
    p = jax.tree_util.tree_map(lambda a: a[li], blk)
    x, valid = attention(x, p, n_valid, dm=dm, lowp=lowp)
    y, route_gap, msb_gate = moe_block(x, p, ids, keep, hi, boost, lo_at,
                                       dm=dm, lowp=lowp, lo_rows=lo_rows)
    return (x + y, jnp.where(valid, route_gap, 0.0),
            jnp.where(valid, msb_gate, 0.0))


def forward(blocks, x, plan, n_valid, lo_at, *, dm, lowp, lo_rows):
    """Every layer over the padded sequence ``x`` [S, d].  ``plan``: the
    ``sequence_plan`` arrays ``ids, keep, hi, boost``, one row per MoE
    layer.  ``dm`` is the frozen (hashable) size table.  Returns the
    residual stream and the widest ``route_gap`` and ``msb_gate``."""
    ids, keep, hi, boost = plan
    route = msb = 0.0
    for li in range(dict(dm)["layers"]):
        x, rg, mg = moe_layer(x, blocks["pos0"], li, ids[li], keep[li],
                              hi[li].astype(jnp.float32), boost[li], n_valid,
                              lo_at, dm=dm, lowp=lowp, lo_rows=lo_rows)
        route = max(route, float(rg.max()))
        msb = max(msb, float(mg.max()))
    return x, route, msb


# ------------------------------------------------------------------- work
def dense_work(dm: dict, kv_len) -> tuple[float, float]:
    """(bytes, FLOPs) of a decode step outside the routed experts: the
    bf16 weights of attention, router, shared expert, norms and
    unembedding, the embedding rows, and the KV rows (keys and values) of
    every valid position of each active sequence; the FLOPs of those
    matmuls and of attention over those rows.  ``kv_len`` holds each
    active sequence's valid KV rows after the step's write."""
    d, V, L = dm["d"], dm["vocab"], dm["layers"]
    H, KV, hd = dm["heads"], dm["kv_heads"], dm["head_dim"]
    E, Fs = dm["experts"], dm["shared_ff"]
    B = len(kv_len)
    rows = float(sum(kv_len))
    attn_w = d * (H + 2 * KV) * hd + H * hd * d
    dense_w = attn_w + d * E + 3 * d * Fs
    per_layer_bytes = (dense_w + 2 * d + (H + 2 * KV) * hd) * BF16 \
        + rows * 2 * KV * hd * BF16
    nbytes = L * per_layer_bytes + (d * V + d + B * d) * BF16
    flops = L * (2.0 * B * dense_w + 4.0 * rows * H * hd) + 2.0 * B * d * V
    return nbytes, flops
