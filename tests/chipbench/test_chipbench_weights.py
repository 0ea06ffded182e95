"""The harness's bf16 weights have the program's parameter layout."""

import chipbench_tiny
import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.cellconfig import dims, family
from repro.models.model import init_params


def test_tree_matches_init_params_at_tiny_size():
    conf = chipbench_tiny.conf()
    dm = dims(conf)
    ours = jax.eval_shape(lambda: weights.draw(dm, 5))
    cfg = family(dm["family"]).model_config(conf, dm)
    theirs = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    shape = lambda t: jax.tree_util.tree_map(lambda a: a.shape, t)  # noqa: E731
    assert jax.tree_util.tree_structure(ours) == \
        jax.tree_util.tree_structure(theirs)
    assert shape(ours) == shape(theirs)
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree_util.tree_leaves(ours))


def test_seeded_and_scaled():
    dm = dims(chipbench_tiny.conf())
    a, b = weights.draw(dm, 2**33 + 1), weights.draw(dm, 2**33 + 1)
    c = weights.draw(dm, 2**33 + 2)
    assert bool(jnp.all(a["unembed"] == b["unembed"]))
    assert not bool(jnp.all(a["unembed"] == c["unembed"]))
    assert float(jnp.std(a["unembed"].astype(jnp.float32))) == \
        pytest_approx(64 ** -0.5)
    assert float(jnp.std(a["embed"].astype(jnp.float32))) == \
        pytest_approx(1.0)
    blk = a["blocks"]["pos0"]
    vectors = jnp.concatenate([
        v.astype(jnp.float32).ravel()
        for v in (a["final_norm"], blk["norm"], blk["moe_norm"], blk["bq"],
                  blk["bk"], blk["bv"])])
    assert float(jnp.std(vectors)) == pytest_approx(weights.VECTOR_STD)


def pytest_approx(x):
    import pytest
    return pytest.approx(x, rel=0.1)
