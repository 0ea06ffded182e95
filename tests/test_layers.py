"""Attention / RoPE / MLP building blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L


def _qkv(key, b=2, sq=16, sk=16, h=4, kv=2, d=32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d))
    k = jax.random.normal(ks[1], (b, sk, kv, d))
    v = jax.random.normal(ks[2], (b, sk, kv, d))
    return q, k, v


class TestRoPE:
    def test_preserves_norm(self, rng):
        x = jax.random.normal(rng, (2, 8, 4, 32))
        pos = jnp.arange(8)[None, :]
        y = L.apply_rope(x, pos, 10000.0)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(x, np.float64), axis=-1),
            np.linalg.norm(np.asarray(y, np.float64), axis=-1), rtol=1e-4)

    def test_relative_property(self, rng):
        """<rope(q,m), rope(k,n)> depends only on m-n."""
        q = jax.random.normal(rng, (1, 1, 1, 16))
        k = jax.random.normal(jax.random.fold_in(rng, 1), (1, 1, 1, 16))

        def score(m, n):
            qr = L.apply_rope(q, jnp.array([[m]]), 1e4)
            kr = L.apply_rope(k, jnp.array([[n]]), 1e4)
            return float(jnp.sum(qr * kr))

        assert abs(score(5, 3) - score(10, 8)) < 1e-4
        assert abs(score(0, 0) - score(7, 7)) < 1e-4

    def test_position_zero_identity(self, rng):
        x = jax.random.normal(rng, (1, 1, 2, 16))
        y = L.apply_rope(x, jnp.zeros((1, 1)), 1e4)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-6)


class TestAttention:
    def test_causal_mask(self, rng):
        """Changing future keys must not change past outputs."""
        q, k, v = _qkv(rng)
        out1 = L.attention(q, k, v, causal=True)
        k2 = k.at[:, -1].set(99.0)
        v2 = v.at[:, -1].set(99.0)
        out2 = L.attention(q, k2, v2, causal=True)
        np.testing.assert_allclose(np.asarray(out1[:, :-1]),
                                   np.asarray(out2[:, :-1]), atol=1e-5)
        assert not np.allclose(np.asarray(out1[:, -1]),
                               np.asarray(out2[:, -1]))

    def test_blockwise_matches_plain(self, rng):
        q, k, v = _qkv(rng, sq=24, sk=40)
        plain = L.attention(q, k, v, causal=True, q_offset=16)
        block = L.blockwise_attention(q, k, v, causal=True, q_offset=16,
                                      block_kv=8)
        np.testing.assert_allclose(np.asarray(plain), np.asarray(block),
                                   atol=1e-4)

    def test_blockwise_sliding_window(self, rng):
        q, k, v = _qkv(rng, sq=16, sk=16)
        plain = L.attention(q, k, v, causal=True, sliding_window=4)
        block = L.blockwise_attention(q, k, v, causal=True,
                                      sliding_window=4, block_kv=8)
        np.testing.assert_allclose(np.asarray(plain), np.asarray(block),
                                   atol=1e-4)

    def test_softcap(self, rng):
        q, k, v = _qkv(rng)
        a = L.attention(q * 10, k * 10, v, causal=True, logit_softcap=5.0)
        assert not np.any(np.isnan(np.asarray(a)))

    def test_decode_matches_full(self, rng):
        """Single-token decode == last row of full attention."""
        q, k, v = _qkv(rng, sq=8, sk=8)
        full = L.attention(q, k, v, causal=True)
        dec = L.decode_attention(q[:, -1], k, v, cur_pos=jnp.asarray(8))
        np.testing.assert_allclose(np.asarray(full[:, -1]), np.asarray(dec),
                                   atol=1e-5)

    def test_decode_ignores_stale_cache(self, rng):
        q, k, v = _qkv(rng, sq=1, sk=16)
        d1 = L.decode_attention(q[:, 0], k, v, cur_pos=jnp.asarray(4))
        k2 = k.at[:, 10:].set(7.0)
        d2 = L.decode_attention(q[:, 0], k2, v, cur_pos=jnp.asarray(4))
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-6)

    def test_gqa_equals_repeated_mha(self, rng):
        q, k, v = _qkv(rng, h=8, kv=2)
        gqa = L.attention(q, k, v, causal=True)
        kr = L._expand_kv(k, 4)
        vr = L._expand_kv(v, 4)
        mha = L.attention(q, kr, vr, causal=True)
        np.testing.assert_allclose(np.asarray(gqa), np.asarray(mha),
                                   atol=1e-5)


class TestMLP:
    @pytest.mark.parametrize("mlp_type", ["swiglu", "geglu", "relu2", "gelu"])
    def test_shapes_and_finiteness(self, rng, mlp_type):
        d, f = 32, 64
        shapes = L.mlp_param_shapes(d, f, mlp_type)
        params = {k: jax.random.normal(jax.random.fold_in(rng, i), s) * 0.05
                  for i, (k, s) in enumerate(shapes.items())}
        x = jax.random.normal(rng, (4, d))
        y = L.mlp_apply(params, x, mlp_type)
        assert y.shape == (4, d)
        assert np.isfinite(np.asarray(y)).all()

    def test_relu2_nonnegative_preactivation(self, rng):
        """Squared-ReLU output is a nonneg combination of wo rows."""
        d, f = 16, 32
        params = {"wi": jax.random.normal(rng, (d, f)),
                  "wo": jnp.eye(f)[:, :d].astype(jnp.float32) * 0 + 1}
        x = jax.random.normal(rng, (4, d))
        h = np.square(np.maximum(np.asarray(x @ params["wi"]), 0))
        assert (h >= 0).all()


class TestNorms:
    def test_rmsnorm_scale_invariant_direction(self, rng):
        x = jax.random.normal(rng, (4, 32))
        s = jnp.zeros(32)
        y1 = L.rms_norm(x, s)
        y2 = L.rms_norm(x * 10.0, s)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)

    def test_layernorm_zero_mean(self, rng):
        x = jax.random.normal(rng, (4, 32)) + 3.0
        y = L.layer_norm(x, jnp.ones(32), jnp.zeros(32))
        np.testing.assert_allclose(np.asarray(y).mean(-1), 0.0, atol=1e-5)


# DeepSeek-V2-Lite's rope part and rope scaling.
YARN = L.YarnScaling(factor=40.0, original_max_position=4096, beta_fast=32.0,
                     beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)


class TestYarn:
    """Pinned to values worked out by hand from the formulas: over 64
    rope dims at theta 1e4, cd(32) = 10.47 and cd(1) = 22.51, so the
    ramp runs from lo = 10 to hi = 23; g(40, 0.707) = 1.2608038."""

    @pytest.mark.parametrize("i, want", [
        (0, 1.0),                          # below lo: theta^0
        (10, 10 ** -1.25),                 # at lo: unscaled
        (16, 0.0055),                      # 0.01 * (6/13 / 40 + 7/13)
        (23, 10 ** (-4 * 46 / 64) / 40),   # at hi: scaled by 1/40
        (31, 10 ** (-4 * 62 / 64) / 40),
    ])
    def test_inv_freq(self, i, want):
        got = L.yarn_inv_freq(64, 1e4, YARN)
        assert got.shape == (32,)
        assert float(got[i]) == pytest.approx(want, rel=1e-5)

    def test_softmax_scale_and_cos_factor(self):
        assert L.mla_softmax_scale(192, YARN) == pytest.approx(
            192 ** -0.5 * 1.2608038 ** 2, rel=1e-7)
        assert L.mla_softmax_scale(192, YARN) == pytest.approx(0.1147214,
                                                               rel=1e-6)
        assert L.yarn_attention_factor(YARN) == 1.0
        assert L.mla_softmax_scale(192, None) == 192 ** -0.5

    def test_interleaved_pairs_rotate_together(self, rng):
        """Dims (2i, 2i+1) form pair i: each lands at (i, D/2 + i)
        rotated by position * inv_freq[i]."""
        x = jax.random.normal(rng, (1, 3, 2, 8))
        inv = L.yarn_inv_freq(8, 1e4, YARN)
        pos = jnp.arange(3)[None, :]
        y = np.asarray(L.apply_rope_interleaved(x, pos, inv))
        xn = np.asarray(x)
        for i in range(4):
            a = np.asarray(pos)[0, :, None] * float(inv[i])
            re = xn[..., 2 * i] * np.cos(a) - xn[..., 2 * i + 1] * np.sin(a)
            im = xn[..., 2 * i + 1] * np.cos(a) + xn[..., 2 * i] * np.sin(a)
            np.testing.assert_allclose(y[..., i], re, rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(y[..., 4 + i], im, rtol=1e-5,
                                       atol=1e-6)


class TestWideKeyAttention:
    """Keys wider than values and a given scale (latent attention's
    expanded form), on the plain and the blockwise path."""

    def test_blockwise_matches_plain(self, rng):
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (1, 40, 2, 24))
        k = jax.random.normal(ks[1], (1, 40, 2, 24))
        v = jax.random.normal(ks[2], (1, 40, 2, 8))
        a = L.attention(q, k, v, causal=True, scale=0.3)
        b = L.blockwise_attention(q, k, v, causal=True, scale=0.3,
                                  block_kv=16)
        assert a.shape == (1, 40, 2, 8)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
        c = L.attention(q, k, v, causal=True)
        assert not np.allclose(np.asarray(a), np.asarray(c), atol=1e-3)


def _mla_cfg(first_k_dense=1, n_layers=3):
    from repro.configs.base import ModelConfig
    from repro.models.moe import MoECfg

    return ModelConfig(
        name="mla-tiny", arch_type="moe", n_layers=n_layers, d_model=64,
        n_heads=4, n_kv_heads=4, head_dim=24, d_ff=96, vocab_size=128,
        moe=MoECfg(8, 2, 32, n_shared_experts=1, d_ff_shared=64,
                   capacity_factor=20.0, norm_topk=False),
        attn_kind="mla", kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        rope_scaling=L.YarnScaling(40.0, 64, 32.0, 1.0, 0.707, 0.707),
        first_k_dense=first_k_dense, norm_eps=1e-6, dtype="float32")


def _mla_params(cfg, seed=0):
    from repro.models import model as MDL

    params = MDL.init_params(cfg, jax.random.PRNGKey(seed))
    # Norm scales start at zero; give them values so a misplaced norm
    # shows.
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.3 * jax.random.normal(
            jax.random.PRNGKey(len(jax.tree_util.keystr(path))), a.shape)
        if jax.tree_util.keystr(path).endswith("norm']") else a, params)


class TestLatentAttention:
    def test_prefill_then_decode_matches_forward(self):
        from repro.models import model as MDL

        cfg = _mla_cfg()
        params = _mla_params(cfg)
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, 128)
        h, _ = MDL.forward(params, cfg, toks)
        full = np.asarray(MDL.unembed(params, cfg, h))
        lg, cache, _ = MDL.prefill(params, cfg, toks[:, :8], 40)
        # 40 latent values a row (rank 32 + rope 8), stored in 128 lanes.
        assert cache["pos0"]["ckv"].shape == (2, 2, 48, 128)
        assert cache["prefix"]["ckv"].shape == (1, 2, 48, 128)
        assert not np.asarray(cache["pos0"]["ckv"][..., 40:]).any()
        np.testing.assert_allclose(np.asarray(lg), full[:, 7], atol=1e-4)
        for t in range(8, 11):
            lg, cache, _ = MDL.decode_step(params, cfg, toks[:, t], cache)
            np.testing.assert_allclose(np.asarray(lg), full[:, t],
                                       atol=1e-4)

    def test_absorbed_decode_matches_expanded(self):
        """The absorbed decode (query through W_UK, the latent cache as
        multi-query attention, W_UV on the output) against attention over
        keys and values up-projected from the same cache rows."""
        from repro.models import model as MDL

        cfg = _mla_cfg(first_k_dense=0, n_layers=1)
        p = jax.tree_util.tree_map(lambda a: a[0],
                                   _mla_params(cfg)["blocks"]["pos0"])
        B, S = 3, 48
        ks = jax.random.split(jax.random.PRNGKey(5), 2)
        x = jax.random.normal(ks[0], (B, 1, 64))
        cache = jnp.pad(jax.random.normal(ks[1], (B, S, 40)),
                        ((0, 0), (0, 0), (0, 88)))
        pos = jnp.array([3, 17, 40])
        got, new = MDL._mla_decode_block(p, x, cfg, cache, pos=pos,
                                         positions=pos[:, None],
                                         vector_pos=True)
        h = L.rms_norm(x, p["norm"], cfg.norm_eps)
        q, row = MDL._mla_qkv(p, h, cfg, pos[:, None])
        np.testing.assert_allclose(np.asarray(new[jnp.arange(B), pos]),
                                   np.pad(np.asarray(row[:, 0]),
                                          ((0, 0), (0, 88))), atol=1e-6)
        k, v = MDL._mla_expand(p, new, cfg)
        s = jnp.einsum("bhd,bshd->bhs", q[:, 0], k) * MDL._mla_scale(cfg)
        valid = jnp.arange(S)[None, :] <= pos[:, None]
        pr = jax.nn.softmax(jnp.where(valid[:, None], s, -1e30), -1)
        o = jnp.einsum("bhs,bshd->bhd", pr, v).reshape(B, -1)
        want = x[:, 0] + o @ p["wo"]
        np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(want),
                                   atol=2e-5)

    def test_dense_prefix_changes_only_layer_0(self):
        """A model with one leading dense layer is that layer, then the
        model without it on that layer's output: the MoE layers keep the
        same params, and the engine counts MoE layers only."""
        import dataclasses

        from repro.core.amat import MatConfig
        from repro.core.engine import EngineConfig, PersistentEngine
        from repro.models import model as MDL

        cfg = _mla_cfg()
        rest = dataclasses.replace(cfg, n_layers=2, first_k_dense=0)
        shapes = MDL.param_shapes(cfg)
        assert set(shapes["blocks"]) == {"prefix", "pos0"}
        assert "mlp" in shapes["blocks"]["prefix"]
        assert "moe" not in shapes["blocks"]["prefix"]
        assert shapes["blocks"]["pos0"] == \
            MDL.param_shapes(rest)["blocks"]["pos0"]

        params = _mla_params(cfg)
        toks = jax.random.randint(jax.random.PRNGKey(3), (1, 10), 0, 128)
        positions = jnp.arange(10)[None]
        pre = jax.tree_util.tree_map(lambda a: a[0],
                                     params["blocks"]["prefix"])
        x = MDL.embed_inputs(params, cfg, toks, None)
        x, _ = MDL._self_attn_block(pre, x, cfg, causal=True,
                                    positions=positions, window=None)
        x, _ = MDL._ffn_block(pre, x, cfg, MDL.PREFIX_SPEC, collect=False)

        def moe_layer(c, pp, layer, _):
            c, _ = MDL._self_attn_block(pp["pos0"], c, rest, causal=True,
                                        positions=positions, window=None)
            c, _ = MDL._ffn_block(pp["pos0"], c, rest,
                                  rest.block_pattern[0], collect=False)
            return c, None

        y, _ = MDL._scan_periods(moe_layer, x,
                                 {"pos0": params["blocks"]["pos0"]}, rest)
        y = L.rms_norm(y, params["final_norm"], cfg.norm_eps)
        h, aux = MDL.forward(params, cfg, toks, collect_trace=True)
        np.testing.assert_allclose(np.asarray(h), np.asarray(y), atol=1e-5)
        assert aux["moe"]["ids"].shape[:2] == (2, 1)

        eng = PersistentEngine(cfg, params, EngineConfig(
            mat=MatConfig(8, 4), cache_bytes=1e6, max_seq=32))
        assert eng.n_moe_layers == 2
        assert eng.layer_map == {(0, 0): 0, (0, 1): 1}
        cache = eng.init_batch_cache(2)
        assert set(cache) == {"pos", "pos0", "prefix"}
        # Three layers of 2 slots x 32 rows x 128 lanes (40 latent
        # values), and the positions.
        assert eng.kv_cache_bytes == 3 * 2 * 32 * 128 * 4 + 2 * 4
