"""A decoder family for the tests alone: ``mha_moe`` with one leading dense
SwiGLU layer (``first_k_dense_replace`` 1) and the top-k gates as the
configuration's ``norm_topk_prob`` states them.  It has the reference's
side only (no ``model_config``): the program serves no such decoder.

It reuses ``mha_moe``'s attention, its MoE layer and the shared MoE half,
so it shows what a new family file has to bring and nothing more.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp

from chipbench import cellconfig, weights
from chipbench.reference import rms, swiglu
from chipbench.work import BF16

mha = cellconfig.family("mha_moe", os.path.join(cellconfig.HERE, "families"))


def dims(conf, common):
    if common["moe_layers"] != tuple(range(1, common["layers"])):
        raise ValueError("this family has exactly one leading dense layer")
    return dict(common, **mha.attention_dims(conf))


def param_shapes(dm):
    d, F, n = dm["d"], dm["dense_ff"], len(dm["moe_layers"])
    dense = dict(mha.attention_shapes(dm, 1), mlp_norm=(1, d),
                 mlp={"wi": (1, d, 2 * F), "wo": (1, F, d)})
    moe = dict(mha.attention_shapes(dm, n), moe_norm=(n, d),
               moe=weights.moe_shapes(dm, n))
    return {"embed": (dm["vocab"], d),
            "blocks": {"dense": dense, "moe": moe},
            "final_norm": (d,), "unembed": (d, dm["vocab"])}


@partial(jax.jit, static_argnames=("dm", "lowp"))
def dense_layer(x, blk, n_valid, *, dm, lowp):
    dm = dict(dm)
    p = jax.tree_util.tree_map(lambda a: a[0], blk)
    x, _ = mha.attention(x, p, n_valid, dm=dm, lowp=lowp)
    h = rms(x, p["mlp_norm"], dm["eps"])
    return x + swiglu(h, p["mlp"]["wi"].astype(jnp.float32),
                      p["mlp"]["wo"].astype(jnp.float32), lowp)


def forward(blocks, x, plan, n_valid, lo_at, *, dm, lowp, lo_rows):
    x = dense_layer(x, blocks["dense"], n_valid, dm=dm, lowp=lowp)
    ids, keep, hi, boost = plan
    route = msb = 0.0
    for m in range(len(dict(dm)["moe_layers"])):
        x, rg, mg = mha.moe_layer(x, blocks["moe"], m, ids[m], keep[m],
                                  hi[m].astype(jnp.float32), boost[m],
                                  n_valid, lo_at, dm=dm, lowp=lowp,
                                  lo_rows=lo_rows)
        route = max(route, float(rg.max()))
        msb = max(msb, float(mg.max()))
    return x, route, msb


def dense_work(dm, kv_len):
    """``mha_moe``'s count, with the dense layer's SwiGLU in place of the
    router and shared expert in the first layer."""
    nbytes, flops = mha.dense_work(dm, kv_len)
    d = dm["d"]
    swap = 3 * d * dm["dense_ff"] - d * dm["experts"] - 3 * d * dm["shared_ff"]
    return nbytes + swap * BF16, flops + 2.0 * len(kv_len) * swap
