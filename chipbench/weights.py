"""Seeded random weights of a cell, drawn on the device in one jitted call.

The tree has the layout the program takes for the cell's decoder family
(the family's ``param_shapes``: leaves under ``blocks`` stacked over
layers, an untied unembedding), in bf16, the type it serves. A vector (a
norm's scale, a q/k/v bias; one per layer where stacked) is normal with
standard deviation ``VECTOR_STD``, so a program that drops a bias or
misapplies a norm gives other logits; the embedding is standard normal;
every other leaf is normal with standard deviation ``fan_in ** -0.5``
(``fan_in`` = the second-to-last dimension), so every layer's output
keeps unit scale. The embedding departs from the program's own
initialiser, which scales it by ``vocab ** -0.5``: rows that small leave
the residual stream to the attention output, which at long prompts is
nearly the same average for every position, and the router then sends
most of a prompt's tokens to the same few experts. Unit rows keep
routing a function of the token, as in a trained model. The reference
draws the same tree from the same seed.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench.cellconfig import family

VECTOR_STD = 0.5


def moe_shapes(dm: dict, n: int) -> dict:
    """The MoE block's leaves, stacked over ``n`` layers: router, routed
    SwiGLU experts, and the shared expert where there is one."""
    d, E = dm["d"], dm["experts"]
    F, Fs = dm["expert_ff"], dm["shared_ff"]
    moe = {"w_router": (n, d, E),
           "experts": {"wi": (n, E, d, 2 * F), "wo": (n, E, F, d)}}
    if Fs:
        moe["shared"] = {"wi": (n, d, 2 * Fs), "wo": (n, Fs, d)}
    return moe


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, 2**31 and past it."""
    s = int(seed) % 2**64
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, s & 0xFFFFFFFF)
    return jax.random.fold_in(key, s >> 32)


@partial(jax.jit, static_argnums=1)
def _draw(key, frozen_shapes):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        _thaw(frozen_shapes), is_leaf=_is_shape)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, shape), k in zip(leaves, keys):
        w = jax.random.normal(k, shape, jnp.bfloat16)
        stacked = path[0].key == "blocks"
        if len(shape) - stacked == 1:
            w = w * jnp.bfloat16(VECTOR_STD)
        elif path[-1].key != "embed":
            w = w * jnp.bfloat16(shape[-2] ** -0.5)
        out.append(w)
    return jax.tree_util.tree_unflatten(treedef, out)


def _freeze(tree):
    if isinstance(tree, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in tree.items()))
    return tree


def _thaw(frozen):
    if isinstance(frozen, tuple) and frozen and \
            isinstance(frozen[0], tuple) and len(frozen[0]) == 2 and \
            isinstance(frozen[0][0], str):
        return {k: _thaw(v) for k, v in frozen}
    return frozen


def draw(dm: dict, seed: int) -> dict:
    """The cell's bf16 weights for ``seed``, made on the default device."""
    return _draw(seed_key(seed),
                 _freeze(family(dm["family"]).param_shapes(dm)))
