"""MoE dispatch/combine + quantized expert path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# jitted MoE end-to-end paths: runs in the CI 'slow' job (pytest -m slow), not the fast tier-1 gate.
pytestmark = pytest.mark.slow
from hypothesis import given, settings, strategies as st

from repro.core.amat import MAT84, amat_quantize
from repro.models.moe import (MoECfg, RoutingPolicy, capacity, combine,
                              dispatch, dispatch_indices, moe_apply,
                              moe_param_shapes, topk_select)


def _params(key, d, cfg: MoECfg):
    shapes = moe_param_shapes(d, cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    ks = jax.random.split(key, len(leaves))
    init = [jax.random.normal(k, s) * 0.1 for k, s in zip(ks, leaves)]
    return jax.tree_util.tree_unflatten(treedef, init)


CFG = MoECfg(n_experts=8, top_k=2, d_ff=32, capacity_factor=4.0)
D = 32   # >= quant group size (G32) for the quantized-expert tests


class TestDispatch:
    def test_positions_unique_per_expert(self, rng):
        probs = jax.nn.softmax(jax.random.normal(rng, (64, 8)), -1)
        gates, ids = topk_select(probs, 2)
        cap = capacity(64, 2, 8, 4.0)
        pos, keep = dispatch_indices(ids, gates, 8, cap)
        ids_np, pos_np, keep_np = map(np.asarray, (ids, pos, keep))
        seen = set()
        for t in range(64):
            for kk in range(2):
                if keep_np[t, kk]:
                    slot = (ids_np[t, kk], pos_np[t, kk])
                    assert slot not in seen, "double-booked expert slot"
                    seen.add(slot)
                    assert pos_np[t, kk] < cap

    def test_roundtrip_identity_when_experts_identity(self, rng):
        """dispatch -> (identity expert) -> combine == gate-weighted sum."""
        T = 32
        x = jax.random.normal(rng, (T, D))
        probs = jax.nn.softmax(jax.random.normal(
            jax.random.fold_in(rng, 1), (T, 8)), -1)
        gates, ids = topk_select(probs, 2)
        cap = capacity(T, 2, 8, 4.0)
        pos, keep = dispatch_indices(ids, gates, 8, cap)
        buf = dispatch(x, ids, pos, keep, 8, cap)
        y = combine(buf, ids, pos, keep, gates)
        # identity experts: y == sum_k gate_k * x == x (gates normalized)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-5)

    def test_capacity_drops_counted(self, rng):
        """With tiny capacity, overflow tokens are dropped, not corrupted."""
        T = 64
        x = jnp.ones((T, D))
        ids = jnp.zeros((T, 1), jnp.int32)       # all to expert 0
        gates = jnp.ones((T, 1))
        cap = 8
        pos, keep = dispatch_indices(ids, gates, 8, cap)
        assert int(np.asarray(keep).sum()) == 8
        buf = dispatch(x, ids, pos, keep, 8, cap)
        assert float(jnp.sum(buf[0])) == pytest.approx(8 * D)
        assert float(jnp.sum(buf[1:])) == 0.0


class TestMoEApply:
    def test_output_shape_and_aux(self, rng):
        params = _params(rng, D, CFG)
        x = jax.random.normal(rng, (32, D))
        y, aux = moe_apply(params, x, CFG)
        assert y.shape == (32, D)
        assert float(aux["aux_loss"]) > 0
        assert float(aux["dropped_frac"]) < 0.2

    def test_quantized_matches_float_closely(self, rng):
        params = _params(rng, D, CFG)
        x = jax.random.normal(rng, (32, D)) * 0.5
        y_float, aux_f = moe_apply(params, x, CFG)

        qp = dict(params)
        qp["experts"] = {
            "wi_q": amat_quantize(params["experts"]["wi"], MAT84),
            "wo_q": amat_quantize(params["experts"]["wo"], MAT84),
        }
        y_q, _ = moe_apply(qp, x, CFG, mat=MAT84,
                           gate_override=(aux_f["gates"], aux_f["ids"]))
        rel = float(jnp.linalg.norm(y_q - y_float)
                    / (jnp.linalg.norm(y_float) + 1e-9))
        assert rel < 0.05, f"8-bit expert path diverges: rel={rel}"

    def test_quant_execution_matches_dense_dequant(self, rng):
        """Tentpole parity: the packed-code kernel path must reproduce
        the gather-then-dequantize path at f32 to kernel-accumulation
        accuracy, for every use_lsb mask shape."""
        params = _params(rng, D, CFG)
        x = jax.random.normal(rng, (32, D)) * 0.5
        _, aux_f = moe_apply(params, x, CFG)
        go = (aux_f["gates"], aux_f["ids"])
        qp = dict(params)
        qp["experts"] = {
            "wi_q": amat_quantize(params["experts"]["wi"], MAT84),
            "wo_q": amat_quantize(params["experts"]["wo"], MAT84),
        }
        for ul in (None, jnp.ones(8, bool), jnp.zeros(8, bool),
                   jnp.arange(8) % 3 == 0):
            y_dense, _ = moe_apply(qp, x, CFG, mat=MAT84,
                                   gate_override=go, use_lsb=ul,
                                   quant_execution=False)
            y_kern, _ = moe_apply(qp, x, CFG, mat=MAT84,
                                  gate_override=go, use_lsb=ul,
                                  quant_execution=True)
            np.testing.assert_allclose(np.asarray(y_kern),
                                       np.asarray(y_dense), atol=1e-4)

    def test_use_lsb_selects_precision(self, rng):
        params = _params(rng, D, CFG)
        x = jax.random.normal(rng, (16, D)) * 0.5
        qp = dict(params)
        qp["experts"] = {
            "wi_q": amat_quantize(params["experts"]["wi"], MAT84),
            "wo_q": amat_quantize(params["experts"]["wo"], MAT84),
        }
        _, aux = moe_apply(params, x, CFG)
        go = (aux["gates"], aux["ids"])
        y_hi, _ = moe_apply(qp, x, CFG, mat=MAT84, gate_override=go,
                            use_lsb=jnp.ones(8, bool))
        y_lo, _ = moe_apply(qp, x, CFG, mat=MAT84, gate_override=go,
                            use_lsb=jnp.zeros(8, bool))
        # 4-bit path differs measurably from 8-bit path
        assert float(jnp.linalg.norm(y_hi - y_lo)) > 1e-4

    def test_policy_dbsc_demand_consistent(self, rng):
        params = _params(rng, D, CFG)
        x = jax.random.normal(rng, (16, D))
        policy = RoutingPolicy(kind="cache_prior", slice_mode="dbsc",
                               theta=0.5)
        state = {"alpha": jnp.float32(0.0),
                 "cached_msb": jnp.ones(8, bool),
                 "cached_lsb": jnp.ones(8, bool)}
        y, aux = moe_apply(params, x, CFG, policy=policy, policy_state=state)
        ids, crit = np.asarray(aux["ids"]), np.asarray(aux["critical"])
        msb, lsb = np.asarray(aux["msb_needed"]), np.asarray(aux["lsb_needed"])
        # every selected expert demands its MSB
        assert msb[np.unique(ids)].all()
        # lsb demand only from critical selections
        crit_experts = np.unique(ids[crit]) if crit.any() else np.array([], int)
        assert set(np.nonzero(lsb)[0]) == set(crit_experts.tolist())

    @pytest.mark.parametrize("kind", ["topk", "cache_prior", "cumsum",
                                      None])
    def test_raw_gates_weight_outputs_criticality_reads_renormalised(
            self, rng, kind):
        """``norm_topk`` false: each row's output is the renormalised
        one times the sum of its raw router probabilities at the served
        ids, while the selections, the traced gates and criticality do
        not move.  A masked row stays zero either way."""
        params = _params(rng, D, CFG)
        x = jax.random.normal(jax.random.fold_in(rng, 7), (16, D))
        mask = jnp.arange(16) != 5
        kw = {"token_mask": mask}
        if kind is not None:
            kw.update(policy=RoutingPolicy(kind=kind, slice_mode="dbsc",
                                           theta=0.5, cumsum_kmax=3),
                      policy_state={"alpha": jnp.float32(0.5),
                                    "cached_msb": jnp.arange(8) < 4,
                                    "cached_lsb": jnp.ones(8, bool)})
        raw_cfg = dataclasses.replace(CFG, norm_topk=False)
        y_n, aux_n = moe_apply(params, x, CFG, **kw)
        y_r, aux_r = moe_apply(params, x, raw_cfg, **kw)
        for key in aux_n:
            np.testing.assert_array_equal(np.asarray(aux_n[key]),
                                          np.asarray(aux_r[key]))
        probs = np.asarray(jax.nn.softmax(x @ params["w_router"], -1))
        ids = np.asarray(aux_n["ids"])
        live = ids < 8
        if "active" in aux_n:
            live &= np.asarray(aux_n["active"])
        mass = np.where(live, np.take_along_axis(
            probs, np.minimum(ids, 7), -1), 0.0).sum(-1)
        assert float(mass.max()) < 0.99
        np.testing.assert_allclose(np.asarray(y_r),
                                   mass[:, None] * np.asarray(y_n),
                                   rtol=1e-4, atol=1e-6)
        assert not np.asarray(y_r)[5].any()

    def test_shared_expert_added(self, rng):
        cfg_s = dataclasses.replace(CFG, n_shared_experts=1, d_ff_shared=32)
        params = _params(rng, D, cfg_s)
        x = jax.random.normal(rng, (16, D))
        y_with, _ = moe_apply(params, x, cfg_s)
        p2 = dict(params)
        p2["shared"] = jax.tree_util.tree_map(jnp.zeros_like, params["shared"])
        y_without, _ = moe_apply(p2, x, cfg_s)
        assert float(jnp.linalg.norm(y_with - y_without)) > 1e-4


class TestPropertyBased:
    @settings(max_examples=20, deadline=None)
    @given(T=st.integers(4, 64), E=st.sampled_from([4, 8, 16]),
           k=st.integers(1, 3), seed=st.integers(0, 999))
    def test_combine_bounded_by_max_expert_output(self, T, E, k, seed):
        """Gate-weighted combine is a convex mix (no amplification)."""
        key = jax.random.PRNGKey(seed)
        x = jax.random.normal(key, (T, D))
        probs = jax.nn.softmax(
            jax.random.normal(jax.random.fold_in(key, 1), (T, E)), -1)
        gates, ids = topk_select(probs, min(k, E))
        cap = capacity(T, min(k, E), E, 8.0)
        pos, keep = dispatch_indices(ids, gates, E, cap)
        buf = dispatch(x, ids, pos, keep, E, cap)
        y = combine(buf, ids, pos, keep, gates)
        assert float(jnp.max(jnp.abs(y))) <= float(jnp.max(jnp.abs(x))) + 1e-4


class TestStackedExpertPeriods:
    """The period scans hand the MoE the expert codes of every period and
    the period index (models/model.py ``_scan_periods``).  At tiny widths
    with three periods of distinct expert weights, prefill and decode with
    quantized execution must equal a reference whose float expert weights
    (the same dequantized codes) are sliced per period by the scan
    itself: an index stuck on one period, or off by one, breaks that."""

    @pytest.fixture(scope="class")
    def setup(self):
        from repro.configs.base import get_config
        from repro.core.slices import quantize_moe_params
        from repro.models import model as MDL
        from repro.quant.groupquant import dequantize

        base = get_config("qwen15-moe-repro")
        cfg = dataclasses.replace(
            base, n_layers=3, dtype="float32",
            moe=dataclasses.replace(base.moe, n_experts=8, top_k=2))
        assert cfg.n_periods == 3
        params = MDL.init_params(cfg, jax.random.PRNGKey(0))
        qp, _, _ = quantize_moe_params(params, cfg, MAT84)

        def with_experts(fn):
            blocks = {}
            for key, blk in qp["blocks"].items():
                if "moe" in blk:
                    ex = blk["moe"]["experts"]
                    blk = {**blk, "moe": {**blk["moe"], "experts": fn(ex)}}
                blocks[key] = blk
            return {**qp, "blocks": blocks}

        ref = with_experts(lambda ex: {"wi": dequantize(ex["wi_q"]),
                                       "wo": dequantize(ex["wo_q"])})
        period0 = with_experts(lambda ex: jax.tree.map(
            lambda a: jnp.broadcast_to(a[:1], a.shape), ex))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                  cfg.vocab_size)
        return cfg, qp, ref, period0, toks

    @staticmethod
    def _serve(cfg, params, toks, quant_execution):
        """Prefill logits, then the logits of two greedy decode steps."""
        from repro.models import model as MDL

        logits, cache, _ = MDL.prefill(params, cfg, toks, 16, mat=MAT84,
                                       quant_execution=quant_execution)
        out = [logits]
        for _ in range(2):
            tok = jnp.argmax(out[-1], -1).astype(jnp.int32)
            logits, cache, _ = MDL.decode_step(
                params, cfg, tok, cache, mat=MAT84,
                quant_execution=quant_execution)
            out.append(logits)
        return [np.asarray(o) for o in out]

    @pytest.fixture(scope="class")
    def runs(self, setup):
        cfg, qp, ref, period0, toks = setup
        return {"want": self._serve(cfg, ref, toks, False),
                "kernel": self._serve(cfg, qp, toks, True),
                "dense": self._serve(cfg, qp, toks, False),
                "period0": self._serve(cfg, period0, toks, True)}

    @pytest.mark.parametrize("step", [0, 1, 2], ids=["prefill", "decode1",
                                                     "decode2"])
    def test_matches_per_period_reference(self, runs, step):
        want = runs["want"][step]
        np.testing.assert_allclose(runs["kernel"][step], want, rtol=1e-5,
                                   atol=1e-5)
        # the dense-dequant path indexes the same stacked leaves
        np.testing.assert_array_equal(runs["dense"][step], want)
        # every period's experts count: period 0's everywhere is far off
        assert np.max(np.abs(runs["period0"][step] - want)) > 1e-2
