"""Decoder family ``mla_moe``: DeepSeek-V2's latent attention (MLA) with
YaRN rope, ``first_k_dense_replace`` leading dense SwiGLU layers, then
the MoE block in every later layer, its top-k gates as the
configuration's ``norm_topk_prob`` states them.

The program serves it as a dense prefix (``blocks/prefix``, stacked
over the leading layers) before one repeating latent attention + MoE
block (``blocks/pos0``, stacked over the MoE layers), with a latent KV
cache of ``kv_lora_rank + qk_rope_head_dim`` values a position and
layer.  Prefill runs the expanded form; decode the absorbed form.

The reference here is the expanded form alone, written from DeepSeek-V2
(arXiv:2405.04434, and the published modelling code's equations) and
independent of the program's: for a normed input ``h``,

* ``q = h W_q``, per head ``[q_nope | q_pe]`` (no query latent);
* ``a = h W_kva`` = ``[c | k_pe]``; ``c <- RMSNorm(c)``; one rope'd
  ``k_pe`` serves every head;
* per head ``[k_nope | v] = c W_kvb``; ``k = [k_nope | rope(k_pe)]``,
  ``q = [q_nope | rope(q_pe)]``;
* ``o = softmax(q k^T s, causal) v``, then ``W_o``.

Rope pairs are stored interleaved: each rope part is de-interleaved
(even dims, then odd) and rotated half against half.  YaRN sets the
frequencies and the softmax scale ``s``; its factor on cos and sin is
``g(factor, mscale) / g(factor, mscale_all_dim)``.

Beside the exports every family gives (see ``mha_moe``), ``mla_work``
counts the absorbed decode attention's work alone, for the kernel's
roofline.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.reference import HI, mm, moe_block, rms, swiglu
from chipbench.work import BF16

F32 = 4


# ------------------------------------------------------------------ sizes
def attention_dims(conf: dict) -> dict:
    if conf.get("q_lora_rank") is not None:
        raise ValueError("a query latent (q_lora_rank) is not served")
    for key, want in (("scoring_func", "softmax"), ("topk_method", "greedy"),
                      ("routed_scaling_factor", 1)):
        if conf.get(key, want) != want:
            raise ValueError(f"{key} {conf[key]!r} is not served")
    rs = conf.get("rope_scaling")
    if rs is not None and rs.get("type") != "yarn":
        raise ValueError(f"rope scaling {rs.get('type')!r} is not served")
    rs = rs or {}
    return {
        "heads": conf["num_attention_heads"],
        "kv_rank": conf["kv_lora_rank"],
        "qk_nope": conf["qk_nope_head_dim"],
        "qk_rope": conf["qk_rope_head_dim"],
        "v_dim": conf["v_head_dim"],
        "rope_theta": float(conf["rope_theta"]),
        "yarn_factor": float(rs.get("factor", 1.0)),
        "yarn_original_max": int(rs.get("original_max_position_embeddings",
                                        0)),
        "yarn_beta_fast": float(rs.get("beta_fast", 32.0)),
        "yarn_beta_slow": float(rs.get("beta_slow", 1.0)),
        "yarn_mscale": float(rs.get("mscale", 1.0)),
        "yarn_mscale_all_dim": float(rs.get("mscale_all_dim", 0.0)),
    }


def dims(conf: dict, common: dict) -> dict:
    first = conf.get("first_k_dense_replace", 0)
    if conf.get("moe_layer_freq", 1) != 1 or \
            common["moe_layers"] != tuple(range(first, common["layers"])):
        raise ValueError("the MoE block must be in every layer after the "
                         "leading dense ones")
    return dict(common, **attention_dims(conf))


def model_config(conf: dict, dm: dict):
    from repro.configs.base import ModelConfig
    from repro.models.layers import YarnScaling
    from repro.models.moe import MoECfg

    eng = conf["engine"]
    ys = None
    if dm["yarn_factor"] != 1.0:
        ys = YarnScaling(
            factor=dm["yarn_factor"],
            original_max_position=dm["yarn_original_max"],
            beta_fast=dm["yarn_beta_fast"], beta_slow=dm["yarn_beta_slow"],
            mscale=dm["yarn_mscale"],
            mscale_all_dim=dm["yarn_mscale_all_dim"])
    return ModelConfig(
        name=conf["name"], arch_type="moe", n_layers=dm["layers"],
        d_model=dm["d"], n_heads=dm["heads"], n_kv_heads=dm["heads"],
        head_dim=dm["qk_nope"] + dm["qk_rope"], d_ff=dm["dense_ff"],
        vocab_size=dm["vocab"], mlp_type="swiglu",
        moe=MoECfg(n_experts=dm["experts"], top_k=dm["top_k"],
                   d_ff=dm["expert_ff"],
                   n_shared_experts=1 if dm["shared_ff"] else 0,
                   d_ff_shared=dm["shared_ff"],
                   capacity_factor=eng["capacity_factor"],
                   mlp_type="swiglu", norm_topk=dm["norm_topk"]),
        attn_kind="mla", kv_lora_rank=dm["kv_rank"],
        qk_nope_head_dim=dm["qk_nope"], qk_rope_head_dim=dm["qk_rope"],
        v_head_dim=dm["v_dim"], rope_scaling=ys,
        first_k_dense=dm["layers"] - len(dm["moe_layers"]),
        rope_theta=dm["rope_theta"], norm_eps=dm["eps"],
        tie_embeddings=False, dtype="bfloat16", source=conf["source"])


# ---------------------------------------------------------------- weights
def attention_shapes(dm: dict, n: int) -> dict:
    """Latent attention's leaves and the norm before it, stacked over
    ``n`` layers."""
    d, H, r = dm["d"], dm["heads"], dm["kv_rank"]
    nope, rope, vd = dm["qk_nope"], dm["qk_rope"], dm["v_dim"]
    return {"wq": (n, d, H * (nope + rope)), "wkv_a": (n, d, r + rope),
            "kv_a_norm": (n, r), "wkv_b": (n, r, H * (nope + vd)),
            "wo": (n, H * vd, d), "norm": (n, d)}


def param_shapes(dm: dict) -> dict:
    d, F = dm["d"], dm["dense_ff"]
    n = len(dm["moe_layers"])
    k = dm["layers"] - n
    blocks = {"pos0": dict(attention_shapes(dm, n), moe_norm=(n, d),
                           moe=weights.moe_shapes(dm, n))}
    if k:
        blocks["prefix"] = dict(attention_shapes(dm, k), mlp_norm=(k, d),
                                mlp={"wi": (k, d, 2 * F), "wo": (k, F, d)})
    return {"embed": (dm["vocab"], d), "blocks": blocks,
            "final_norm": (d,), "unembed": (d, dm["vocab"])}


# -------------------------------------------------------------- reference
def g(scale: float, mscale: float) -> float:
    """YaRN's ``g(s, mu) = 0.1 mu ln s + 1`` (1 for ``s <= 1``)."""
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def inv_freq(dm: dict):
    """The rope part's inverse frequencies, under YaRN where the
    configuration scales rope: ``f_inter (1 - m) + f_extra m`` with
    ``f_extra = theta^(-2i/D)``, ``f_inter = f_extra / factor`` and
    ``m = 1 - clip((i - lo) / (hi - lo), 0, 1)``, ``lo = floor(cd(beta
    fast))``, ``hi = ceil(cd(beta slow))`` clamped to [0, D - 1],
    ``cd(r) = D ln(L0 / (2 pi r)) / (2 ln theta)``."""
    D, theta = dm["qk_rope"], dm["rope_theta"]
    i = jnp.arange(D // 2, dtype=jnp.float32)
    f_extra = theta ** (-2.0 * i / D)
    s = dm["yarn_factor"]
    if s == 1.0:
        return f_extra

    def cd(r):
        return D * math.log(dm["yarn_original_max"] / (2 * math.pi * r)) \
            / (2 * math.log(theta))

    lo = max(math.floor(cd(dm["yarn_beta_fast"])), 0)
    hi = min(math.ceil(cd(dm["yarn_beta_slow"])), D - 1)
    m = 1.0 - jnp.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return f_extra / s * (1.0 - m) + f_extra * m


def softmax_scale(dm: dict) -> float:
    s = (dm["qk_nope"] + dm["qk_rope"]) ** -0.5
    if dm["yarn_factor"] != 1.0 and dm["yarn_mscale_all_dim"]:
        s *= g(dm["yarn_factor"], dm["yarn_mscale_all_dim"]) ** 2
    return s


def rope(x, pos, dm):
    """Rope over [S, H, D] whose pairs are stored interleaved: even dims
    then odd ones, rotated half against half."""
    f = g(dm["yarn_factor"], dm["yarn_mscale"]) \
        / g(dm["yarn_factor"], dm["yarn_mscale_all_dim"])
    ang = pos[:, None].astype(jnp.float32) * inv_freq(dm)
    cos, sin = (jnp.cos(ang) * f)[:, None, :], (jnp.sin(ang) * f)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, p, n_valid, *, dm, lowp):
    """Causal latent attention of one layer over a padded sequence, in the
    expanded form, with its norm and residual.  Returns the new residual
    stream and which positions are valid (before ``n_valid``)."""
    S = x.shape[0]
    H, r = dm["heads"], dm["kv_rank"]
    nope, rp, vd = dm["qk_nope"], dm["qk_rope"], dm["v_dim"]
    f32 = jnp.float32
    pos = jnp.arange(S)
    h = rms(x, p["norm"], dm["eps"])
    q = mm(h, p["wq"].astype(f32), lowp).reshape(S, H, nope + rp)
    a = mm(h, p["wkv_a"].astype(f32), lowp)
    c = rms(a[:, :r], p["kv_a_norm"], dm["eps"])
    k_pe = rope(a[:, None, r:], pos, dm)                       # [S, 1, rp]
    kv = mm(c, p["wkv_b"].astype(f32), lowp).reshape(S, H, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (S, H, rp))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, dm)], -1)
    if lowp:
        q, k, v = (t.astype(jnp.float8_e4m3fn).astype(f32) for t in (q, k, v))
    valid = pos < n_valid
    scale = softmax_scale(dm)

    def attend(qb_pos):
        qb, pb = qb_pos
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
        mask = (pb[:, None] >= pos[None, :]) & valid[None, :]
        pr = jax.nn.softmax(jnp.where(mask[None], sc, -1e30), -1)
        if lowp:
            pr = pr.astype(jnp.float8_e4m3fn).astype(f32)
        return jnp.einsum("hqk,khd->qhd", pr, v, precision=HI)

    blk_q = math.gcd(S, 256)
    o = jax.lax.map(attend, (q.reshape(S // blk_q, blk_q, H, nope + rp),
                             pos.reshape(S // blk_q, blk_q)))
    return x + mm(o.reshape(S, H * vd), p["wo"].astype(f32), lowp), valid


@partial(jax.jit, static_argnames=("dm", "lowp"))
def dense_layer(x, blk, li, n_valid, *, dm, lowp):
    """Leading dense layer ``li``: latent attention, then SwiGLU."""
    dm = dict(dm)
    p = jax.tree_util.tree_map(lambda a: a[li], blk)
    x, _ = attention(x, p, n_valid, dm=dm, lowp=lowp)
    h = rms(x, p["mlp_norm"], dm["eps"])
    return x + swiglu(h, p["mlp"]["wi"].astype(jnp.float32),
                      p["mlp"]["wo"].astype(jnp.float32), lowp)


@partial(jax.jit, static_argnames=("dm", "lowp", "lo_rows"))
def moe_layer(x, blk, li, ids, keep, hi, boost, n_valid, lo_at, *, dm, lowp,
              lo_rows):
    """MoE layer ``li`` of the stacked ``blk``: latent attention, then the
    shared MoE block.  Returns the new residual stream, and for each
    position the widest ``route_gap`` and ``msb_gate`` (0 on padding)."""
    dm = dict(dm)
    p = jax.tree_util.tree_map(lambda a: a[li], blk)
    x, valid = attention(x, p, n_valid, dm=dm, lowp=lowp)
    y, route_gap, msb_gate = moe_block(x, p, ids, keep, hi, boost, lo_at,
                                       dm=dm, lowp=lowp, lo_rows=lo_rows)
    return (x + y, jnp.where(valid, route_gap, 0.0),
            jnp.where(valid, msb_gate, 0.0))


def forward(blocks, x, plan, n_valid, lo_at, *, dm, lowp, lo_rows):
    """The leading dense layers, then every MoE layer, over the padded
    sequence ``x`` [S, d].  ``plan``: one row per MoE layer."""
    d = dict(dm)
    for li in range(d["layers"] - len(d["moe_layers"])):
        x = dense_layer(x, blocks["prefix"], li, n_valid, dm=dm, lowp=lowp)
    ids, keep, hi, boost = plan
    route = msb = 0.0
    for li in range(len(d["moe_layers"])):
        x, rg, mg = moe_layer(x, blocks["pos0"], li, ids[li], keep[li],
                              hi[li].astype(jnp.float32), boost[li], n_valid,
                              lo_at, dm=dm, lowp=lowp, lo_rows=lo_rows)
        route = max(route, float(rg.max()))
        msb = max(msb, float(mg.max()))
    return x, route, msb


# ------------------------------------------------------------------- work
def _attention_weights(dm: dict) -> int:
    d, H, r = dm["d"], dm["heads"], dm["kv_rank"]
    nope, rope, vd = dm["qk_nope"], dm["qk_rope"], dm["v_dim"]
    return d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd) \
        + H * vd * d


def mla_work(dm: dict, kv_len) -> tuple[float, float]:
    """(bytes, FLOPs) of the absorbed decode attention alone in one step,
    over every layer: each valid latent row read once (``rank + rope``
    bf16 values), each sequence's queries in (bf16) and its ``[H,
    rank]`` output out (f32); per row and head a score over the whole
    row and a value product over its first ``rank`` values.
    ``kv_len`` holds each active sequence's valid rows after the step's
    write."""
    L, H, r = dm["layers"], dm["heads"], dm["kv_rank"]
    R = r + dm["qk_rope"]
    rows, B = float(sum(kv_len)), len(kv_len)
    nbytes = L * (rows * R * BF16 + B * H * (R * BF16 + r * F32))
    flops = L * rows * H * 2.0 * (R + r)
    return nbytes, flops


def dense_work(dm: dict, kv_len) -> tuple[float, float]:
    """(bytes, FLOPs) of a decode step outside the routed experts: the
    bf16 weights of latent attention in every layer, the dense SwiGLU in
    the leading layers, router and shared expert in the MoE layers,
    norms and unembedding, the embedding rows, and the latent rows of
    every valid position of each active sequence in every layer; the
    FLOPs of those matmuls (the key and value up-projections are applied
    to the query and the output, once each) and of the absorbed
    attention over those rows."""
    d, V, L = dm["d"], dm["vocab"], dm["layers"]
    n_moe = len(dm["moe_layers"])
    B = len(kv_len)
    weights_ = L * _attention_weights(dm) \
        + n_moe * (d * dm["experts"] + 3 * d * dm["shared_ff"]) \
        + (L - n_moe) * 3 * d * dm["dense_ff"]
    norms = L * (2 * d + dm["kv_rank"])
    rows = float(sum(kv_len))
    nbytes = (weights_ + norms + d * V + d + B * d) * BF16 \
        + L * rows * (dm["kv_rank"] + dm["qk_rope"]) * BF16
    flops = 2.0 * B * weights_ + mla_work(dm, kv_len)[1] + 2.0 * B * d * V
    return nbytes, flops
