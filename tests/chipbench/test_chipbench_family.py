"""A decoder family comes in as one file: a family kept with the tests
(``family_fixture/dense_lead.py``: one leading dense layer, raw top-k
gates) is found by its file name alone and runs through the weights, the
reference and the work counts; and the shared MoE half weights expert
outputs by raw gates where ``norm_topk`` is false while criticality
reads the renormalised ones."""

import os

import chipbench_tiny as tiny
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cellconfig, weights
from chipbench.cellconfig import dims
from chipbench.reference import Reference, moe_block, sequence_plan
from chipbench.work import step_work
from test_chipbench_work import DM, _step

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "family_fixture")


@pytest.fixture
def lead(monkeypatch):
    """The tiny configuration as a ``dense_lead`` decoder of three layers,
    with the loader looking in the fixture's directory alone."""
    monkeypatch.setattr(cellconfig, "FAMILIES", FIXTURE)
    conf = dict(tiny.conf(), family="dense_lead", num_hidden_layers=3,
                first_k_dense_replace=1, norm_topk_prob=False)
    return dims(conf)


def test_found_by_file_name(lead):
    assert lead["family"] == "dense_lead"
    assert lead["moe_layers"] == (1, 2)
    assert lead["norm_topk"] is False
    assert cellconfig.family("dense_lead").__file__ == \
        os.path.join(FIXTURE, "dense_lead.py")
    with pytest.raises(KeyError):
        cellconfig.family("mha_moe")


def test_mha_moe_refuses_a_dense_layer():
    conf = dict(tiny.conf(), first_k_dense_replace=1)
    with pytest.raises(ValueError):
        dims(conf)


def test_weights_follow_the_family(lead):
    w = jax.eval_shape(lambda: weights.draw(lead, 3))
    blocks = w["blocks"]
    assert blocks["dense"]["mlp"]["wi"].shape == (1, 64, 2 * 128)
    assert "w_router" not in blocks["dense"]
    assert blocks["moe"]["moe"]["experts"]["wi"].shape == (2, 8, 64, 128)
    assert blocks["moe"]["wq"].shape == (2, 64, 64)


def test_plan_covers_the_moe_layers_only(lead):
    rng = np.random.default_rng(3)
    prefill, steps = tiny.plan(lead, rng, 2)
    ids, keep, hi, boost, bad = sequence_plan(lead, prefill, steps, 3)
    assert bad == 0
    assert ids.shape == (2, 6 + 2, 2) and boost.shape == (2, 8, 8)
    full, steps3 = tiny.plan(lead, rng, 3)
    with pytest.raises(ValueError):
        sequence_plan(lead, full, steps3, 3)


def _reference_run(dm, seed=5):
    rng = np.random.default_rng(11)
    prefill, steps = tiny.plan(dm, rng, len(dm["moe_layers"]))
    *plan, _ = sequence_plan(dm, prefill, steps, 3)
    prompt = rng.integers(0, dm["vocab"], 6).astype(np.int32)
    served = rng.integers(0, dm["vocab"], 3).astype(np.int32)
    ref = Reference(dm, seed, 16, 4)
    lg, route, msb = ref._run(prompt, served, plan, lowp=False)
    got = ref.compare(prompt, served, served, plan)
    return np.asarray(lg)[:3], route, msb, got


def test_reference_runs_the_family(lead):
    lg, route, msb, got = _reference_run(lead)
    assert np.all(np.isfinite(lg))
    assert 0.0 <= route <= 1.0 and 0.0 <= msb <= 1.0
    assert got["logit_gap"] >= 0.0
    # The same sequence with renormalised gates: other logits, the same
    # criticality and routing readings.
    lg_n, route_n, msb_n, _ = _reference_run(dict(lead, norm_topk=True))
    assert not np.allclose(lg, lg_n, rtol=1e-3, atol=1e-3)
    assert msb == msb_n and route == route_n


def test_work_counts_through_the_family(lead):
    ids, active, critical = _step()
    dm = dict(DM, family="dense_lead", dense_ff=48)
    nbytes, flops = step_work(dm, ids, active, critical, [10, 3])
    # mha_moe's count of the same step (test_chipbench_pinned), with the
    # dense SwiGLU in place of router and shared expert in one layer.
    swap = 3 * 64 * 48 - 64 * 4 - 3 * 64 * 16
    assert nbytes == 72192.0 + swap * 2
    assert flops == 156928.0 + 2 * 2 * swap


@pytest.mark.parametrize("norm_topk", [True, False])
def test_moe_block_raw_gates_weight_outputs_criticality_reads_renormalised(
        norm_topk):
    conf = dict(tiny.conf(), shared_expert_intermediate_size=0)
    dm = dict(dims(conf), norm_topk=norm_topk)
    p = jax.tree_util.tree_map(lambda a: a[0],
                               weights.draw(dm, 9)["blocks"]["pos0"])
    rng = np.random.default_rng(4)
    S, k, E = 8, dm["top_k"], dm["experts"]
    x = jnp.asarray(rng.normal(size=(S, dm["d"])), jnp.float32)
    ids = jnp.asarray(np.stack([rng.permutation(E)[:k] for _ in range(S)]),
                      jnp.int32)
    ones = jnp.ones((S, k), jnp.float32)
    args = (x, p, ids, ones, jnp.zeros((S, k), jnp.float32),
            jnp.ones((S, E), jnp.float32), 4)
    y, _, msb = moe_block(*args, dm=dm, lowp=False, lo_rows=4)
    y_n, _, msb_n = moe_block(*args, dm=dict(dm, norm_topk=True), lowp=False,
                              lo_rows=4)
    # No selection was run at MSB+LSB by the trace: each row's msb_gate is
    # its largest renormalised gate, at least 1/k, with either weighting.
    np.testing.assert_array_equal(msb, msb_n)
    assert float(msb.min()) >= 1.0 / k - 1e-6
    if norm_topk:
        np.testing.assert_array_equal(y, y_n)
        return
    # Without a shared expert, raw gates scale each row's output by the sum
    # of its raw gates, which lies under 1.
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + dm["eps"])
    h = h * (1.0 + p["moe_norm"].astype(jnp.float32))
    probs = jax.nn.softmax(jnp.matmul(
        h, p["moe"]["w_router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), -1)
    s = np.asarray(jnp.take_along_axis(probs, ids, -1).sum(-1))
    assert float(s.max()) < 0.99
    np.testing.assert_allclose(np.asarray(y), s[:, None] * np.asarray(y_n),
                               rtol=1e-4, atol=1e-6)
