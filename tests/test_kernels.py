"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# interpret-mode Pallas kernels: runs in the CI 'slow' job (pytest -m slow), not the fast tier-1 gate.
pytestmark = pytest.mark.slow
from hypothesis import given, settings, strategies as st

from repro.core.amat import MAT84, PAPER_CONFIGS, amat_quantize
from repro.kernels.amat_matmul.kernel import amat_batched_matmul_pallas
from repro.kernels.amat_matmul.ops import (amat_expert_matmul_qt,
                                           amat_matmul, amat_matmul_qt)
from repro.kernels.amat_matmul.ref import (amat_batched_matmul_ref,
                                           amat_matmul_ref)
from repro.kernels.expert_matmul.ops import expert_matmul_qt
from repro.kernels.expert_matmul.ref import expert_matmul_ref
from repro.quant.groupquant import quantize

SHAPES_MKN = [
    (8, 32, 16),        # minimal
    (16, 64, 48),       # non-128 N
    (128, 256, 128),    # MXU-aligned
    (7, 96, 33),        # ragged M/N (padding path)
    (1, 32, 128),       # decode-like single token
]


class TestAmatMatmul:
    @pytest.mark.parametrize("mkn", SHAPES_MKN, ids=str)
    @pytest.mark.parametrize("mode,shift", [("high", 0), ("low", 4),
                                            ("low", 2)])
    @pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, rng, mkn, mode, shift, xdtype):
        M, K, N = mkn
        x = jax.random.normal(rng, (M, K)).astype(xdtype)
        w = jax.random.normal(jax.random.fold_in(rng, 1), (K, N)) * 0.1
        qt = quantize(w, bits=8, group_size=32, asymmetric=True)
        out = amat_matmul_qt(x, qt, shift=shift, mode=mode)
        ref = amat_matmul_ref(x, qt.codes, qt.scales, qt.zero_points,
                              group_size=32, shift=shift, mode=mode)
        tol = 1e-4 if xdtype == jnp.float32 else 5e-2
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=tol * max(1.0, float(jnp.max(jnp.abs(ref)))))

    def test_block_size_invariance(self, rng):
        M, K, N = 64, 128, 64
        x = jax.random.normal(rng, (M, K))
        w = jax.random.normal(jax.random.fold_in(rng, 1), (K, N)) * 0.1
        qt = quantize(w, bits=8, group_size=32, asymmetric=True)
        outs = [
            amat_matmul(x, qt.codes, qt.scales, qt.zero_points,
                        bm=bm, bn=bn)
            for bm, bn in [(16, 16), (64, 64), (32, 32)]
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                       atol=1e-4)

    def test_approximates_float_matmul(self, rng):
        """High path should track the unquantized matmul closely."""
        x = jax.random.normal(rng, (32, 128))
        w = jax.random.normal(jax.random.fold_in(rng, 1), (128, 64)) * 0.1
        qt = quantize(w, bits=8, group_size=32, asymmetric=True)
        out = amat_matmul_qt(x, qt)
        exact = x @ w
        rel = float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))
        assert rel < 0.01

    def test_pallas_call_pads_ragged_m(self, rng):
        """Regression: decode batches are rarely multiples of bm — the
        raw pallas entry point must pad M internally, not assert."""
        for M in (1, 7, 130):
            x = jax.random.normal(rng, (M, 64))
            w = jax.random.normal(jax.random.fold_in(rng, M), (64, 128)) * 0.1
            qt = quantize(w, bits=8, group_size=32, asymmetric=True)
            out = amat_batched_matmul_pallas(
                x[None], qt.codes[None], qt.scales[None],
                qt.zero_points[None], jnp.ones(1, bool), bm=128, bn=128,
                interpret=True)[0]
            ref = amat_matmul_ref(x, qt.codes, qt.scales, qt.zero_points)
            assert out.shape == (M, 128)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-3)


class TestAmatBatchedMatmul:
    """The quantized-execution kernel: per-expert use_lsb via scalar
    prefetch, across all paper MAT configs and ragged shapes."""

    @pytest.mark.parametrize("mat", PAPER_CONFIGS, ids=lambda m: m.name)
    @pytest.mark.parametrize("emkn", [(4, 16, 64, 32), (3, 7, 96, 33),
                                      (2, 1, 32, 128), (5, 130, 160, 16)],
                             ids=str)
    def test_matches_ref_paper_configs(self, rng, mat, emkn):
        E, M, K, N = emkn
        x = jax.random.normal(rng, (E, M, K))
        w = jax.random.normal(jax.random.fold_in(rng, 1), (E, K, N)) * 0.1
        qt = amat_quantize(w, mat)
        ul = jnp.arange(E) % 2 == 0               # mixed per-expert mask
        out = amat_expert_matmul_qt(x, qt, ul, shift=mat.shift)
        ref = amat_batched_matmul_ref(x, qt.codes, qt.scales,
                                      qt.zero_points, ul,
                                      group_size=mat.group_size,
                                      shift=mat.shift)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)

    @pytest.mark.parametrize("mat", PAPER_CONFIGS, ids=lambda m: m.name)
    def test_odd_group_count_matches_ref(self, rng, mat):
        """K = 11 groups, as in a wo projection of width 1408 (44 groups):
        the metadata block spans the whole group axis, whatever its
        count."""
        E, M, K, N = 3, 9, 11 * 32, 48
        x = jax.random.normal(rng, (E, M, K))
        w = jax.random.normal(jax.random.fold_in(rng, 1), (E, K, N)) * 0.1
        qt = amat_quantize(w, mat)
        ul = jnp.arange(E) % 2 == 1
        out = amat_expert_matmul_qt(x, qt, ul, shift=mat.shift)
        ref = amat_batched_matmul_ref(x, qt.codes, qt.scales,
                                      qt.zero_points, ul,
                                      group_size=mat.group_size,
                                      shift=mat.shift)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)

    def test_use_lsb_extremes_match_static_modes(self, rng):
        """all-ones == high-bit dequant; all-zeros == AMAT truncation."""
        E, M, K, N = 2, 8, 64, 32
        x = jax.random.normal(rng, (E, M, K))
        w = jax.random.normal(jax.random.fold_in(rng, 1), (E, K, N)) * 0.1
        qt = quantize(w, bits=8, group_size=32, asymmetric=True)
        hi = amat_expert_matmul_qt(x, qt, jnp.ones(E, bool), shift=4)
        lo = amat_expert_matmul_qt(x, qt, jnp.zeros(E, bool), shift=4)
        for e in range(E):
            hi_ref = amat_matmul_ref(x[e], qt.codes[e], qt.scales[e],
                                     qt.zero_points[e], mode="high")
            lo_ref = amat_matmul_ref(x[e], qt.codes[e], qt.scales[e],
                                     qt.zero_points[e], shift=4,
                                     mode="low")
            np.testing.assert_allclose(np.asarray(hi[e]),
                                       np.asarray(hi_ref), atol=1e-4)
            np.testing.assert_allclose(np.asarray(lo[e]),
                                       np.asarray(lo_ref), atol=1e-4)
        assert float(jnp.linalg.norm(hi - lo)) > 1e-3

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 999), E=st.integers(1, 5))
    def test_property_random_masks(self, seed, E):
        key = jax.random.PRNGKey(seed)
        M, K, N = 6, 32, 16
        x = jax.random.normal(key, (E, M, K))
        w = jax.random.normal(jax.random.fold_in(key, 1), (E, K, N)) * 0.1
        qt = quantize(w, bits=8, group_size=32, asymmetric=True)
        ul = jax.random.bernoulli(jax.random.fold_in(key, 2), shape=(E,))
        out = amat_expert_matmul_qt(x, qt, ul, shift=4)
        ref = amat_batched_matmul_ref(x, qt.codes, qt.scales,
                                      qt.zero_points, ul, shift=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)

    def test_block_size_invariance(self, rng):
        E, M, K, N = 2, 32, 128, 64
        x = jax.random.normal(rng, (E, M, K))
        w = jax.random.normal(jax.random.fold_in(rng, 1), (E, K, N)) * 0.1
        qt = quantize(w, bits=8, group_size=32, asymmetric=True)
        ul = jnp.array([True, False])
        outs = [amat_expert_matmul_qt(x, qt, ul, shift=4, bm=bm, bn=bn)
                for bm, bn in [(16, 16), (32, 64), (128, 128)]]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                       atol=1e-4)


class TestStackedPeriods:
    """Codes stacked over periods ([P, E, K, N]) and read at a period
    picked by the index maps must give, bit for bit, what the unstacked
    call on that period's codes gives."""

    @pytest.mark.parametrize("kn", [(64, 256), (1408, 128)],
                             ids=["wi", "wo_k1408"])
    @pytest.mark.parametrize("m", [5, 130])
    def test_each_period_matches_its_own_codes(self, rng, kn, m):
        P, E = 3, 3
        K, N = kn
        x = jax.random.normal(rng, (E, m, K))
        w = jax.random.normal(jax.random.fold_in(rng, 1),
                              (P, E, K, N)) * 0.1
        qt = amat_quantize(w, MAT84)
        ul = jnp.array([True, False, True])
        outs = []
        for layer in range(P):
            own = amat_quantize(w[layer], MAT84)
            want = amat_expert_matmul_qt(x, own, ul, shift=MAT84.shift)
            got = amat_expert_matmul_qt(x, qt, ul, layer, shift=MAT84.shift)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
            outs.append(np.asarray(got))
        # the periods differ, so a map stuck on one period would show
        assert not np.allclose(outs[0], outs[1])
        assert not np.allclose(outs[1], outs[2])

    def test_traced_layer_in_a_scan(self, rng):
        """The layer index as a scan carries it: a traced scalar."""
        P, E, M, K, N = 4, 2, 8, 64, 128
        x = jax.random.normal(rng, (E, M, K))
        w = jax.random.normal(jax.random.fold_in(rng, 2),
                              (P, E, K, N)) * 0.1
        qt = quantize(w, bits=8, group_size=32, asymmetric=True)
        ul = jnp.array([False, True])

        def body(_, layer):
            return None, amat_expert_matmul_qt(x, qt, ul, layer, shift=4)

        _, got = jax.lax.scan(body, None, jnp.arange(P))
        for layer in range(P):
            ref = amat_batched_matmul_ref(
                x, qt.codes[layer], qt.scales[layer],
                qt.zero_points[layer], ul, shift=4)
            np.testing.assert_allclose(np.asarray(got[layer]),
                                       np.asarray(ref), atol=1e-4)


class TestExpertMatmul:
    @pytest.mark.parametrize("eckn", [(4, 16, 64, 32), (8, 33, 96, 128),
                                      (2, 128, 128, 128), (3, 1, 32, 16)],
                             ids=str)
    def test_matches_ref(self, rng, eckn):
        E, C, K, N = eckn
        x = jax.random.normal(rng, (E, C, K))
        w = jax.random.normal(jax.random.fold_in(rng, 1), (E, K, N)) * 0.1
        qt = quantize(w, bits=8, group_size=32, asymmetric=True)
        ul = jnp.arange(E) % 2 == 0
        out = expert_matmul_qt(x, qt, ul, shift=4)
        ref = expert_matmul_ref(x, qt.codes, qt.scales, qt.zero_points, ul,
                                group_size=32, shift=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)

    def test_use_lsb_flag_changes_result(self, rng):
        E, C, K, N = 2, 8, 64, 32
        x = jax.random.normal(rng, (E, C, K))
        w = jax.random.normal(jax.random.fold_in(rng, 1), (E, K, N)) * 0.1
        qt = quantize(w, bits=8, group_size=32, asymmetric=True)
        hi = expert_matmul_qt(x, qt, jnp.ones(E, bool), shift=4)
        lo = expert_matmul_qt(x, qt, jnp.zeros(E, bool), shift=4)
        assert float(jnp.linalg.norm(hi - lo)) > 1e-3

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 999), E=st.integers(1, 6))
    def test_property_random_flags(self, seed, E):
        key = jax.random.PRNGKey(seed)
        C, K, N = 8, 32, 16
        x = jax.random.normal(key, (E, C, K))
        w = jax.random.normal(jax.random.fold_in(key, 1), (E, K, N)) * 0.1
        qt = quantize(w, bits=8, group_size=32, asymmetric=True)
        ul = jax.random.bernoulli(jax.random.fold_in(key, 2), shape=(E,))
        out = expert_matmul_qt(x, qt, ul, shift=4)
        ref = expert_matmul_ref(x, qt.codes, qt.scales, qt.zero_points,
                                ul, group_size=32, shift=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)


class TestFlashAttention:
    @pytest.mark.parametrize(
        "dims",
        [(1, 16, 16, 4, 2, 32, True, None),
         (2, 24, 40, 8, 2, 32, True, None),
         (1, 17, 33, 4, 4, 64, True, 8),      # ragged + sliding window
         (1, 16, 16, 4, 2, 32, False, None)], # non-causal (encoder)
        ids=str)
    def test_matches_ref(self, rng, dims):
        from repro.kernels.flash_attn.ops import flash_attention
        from repro.kernels.flash_attn.ref import flash_attention_ref

        B, Sq, Sk, H, Hkv, D, causal, win = dims
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (B, Sq, H, D))
        k = jax.random.normal(ks[1], (B, Sk, Hkv, D))
        v = jax.random.normal(ks[2], (B, Sk, Hkv, D))
        out = flash_attention(q, k, v, causal=causal, sliding_window=win,
                              bq=8, bk=8)
        ref = flash_attention_ref(q, k, v, causal=causal,
                                  sliding_window=win)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)

    def test_block_size_invariance(self, rng):
        from repro.kernels.flash_attn.ops import flash_attention

        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (1, 32, 4, 32))
        k = jax.random.normal(ks[1], (1, 32, 2, 32))
        v = jax.random.normal(ks[2], (1, 32, 2, 32))
        outs = [flash_attention(q, k, v, bq=bq, bk=bk)
                for bq, bk in [(8, 8), (16, 32), (32, 16)]]
        for o in outs[1:]:
            np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                       atol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 999), sq=st.integers(4, 24),
           sk=st.integers(4, 24))
    def test_property_random_shapes(self, seed, sq, sk):
        from repro.kernels.flash_attn.ops import flash_attention
        from repro.kernels.flash_attn.ref import flash_attention_ref

        key = jax.random.PRNGKey(seed)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (1, sq, 2, 16))
        k = jax.random.normal(ks[1], (1, sk, 2, 16))
        v = jax.random.normal(ks[2], (1, sk, 2, 16))
        out = flash_attention(q, k, v, bq=8, bk=8)
        ref = flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)
