"""End-to-end system tests: train-improves-loss, serve pipeline,
quantized-decode fidelity — the integration layer above the unit tests."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# full-system integration: runs in the CI 'slow' job (pytest -m slow), not the fast tier-1 gate.
pytestmark = pytest.mark.slow

from repro.configs.base import get_config
from repro.core.amat import MatConfig
from repro.core.engine import EngineConfig, SliceMoEEngine
from repro.launch.train import train_loop
from repro.models.moe import RoutingPolicy
from repro.models.model import init_params
from repro.optim import adamw as OPT
from repro.serving.server import Request, SliceMoEServer


@pytest.mark.slow
class TestTraining:
    def test_loss_decreases_dense(self):
        cfg = get_config("smollm-360m").reduced()
        _, _, hist = train_loop(cfg, steps=25, global_batch=4, seq_len=32,
                                opt_cfg=OPT.AdamWConfig(
                                    lr=3e-3, total_steps=25, warmup_steps=2),
                                log_every=1000, collect_history=True)
        losses = [h["loss"] for h in hist]
        assert losses[-1] < losses[0] - 0.1, losses

    def test_loss_decreases_moe(self):
        cfg = get_config("qwen15-moe-repro")
        cfg = dataclasses.replace(cfg, n_layers=2)
        _, _, hist = train_loop(cfg, steps=20, global_batch=4, seq_len=32,
                                opt_cfg=OPT.AdamWConfig(
                                    lr=3e-3, total_steps=20, warmup_steps=2),
                                log_every=1000, collect_history=True)
        losses = [h["loss"] for h in hist]
        assert losses[-1] < losses[0] - 0.05, losses


class TestServing:
    def test_server_moe_arch(self):
        cfg = get_config("deepseek-v2-lite-repro")
        cfg = dataclasses.replace(cfg, n_layers=2)
        params = init_params(cfg, jax.random.PRNGKey(0))
        server = SliceMoEServer(
            cfg, params,
            engine_cfg=EngineConfig(
                mat=MatConfig(8, 4), cache_bytes=1e6,
                policy=RoutingPolicy(kind="cache_prior"),
                miss_rate_target=0.1),
            max_seq=64)
        rng = np.random.default_rng(0)
        for i in range(2):
            server.submit(Request(
                request_id=i,
                prompt=rng.integers(0, cfg.vocab_size, 24).astype(np.int32),
                max_new_tokens=8))
        done = server.run()
        assert len(done) == 2
        for c in done:
            assert len(c.tokens) == 8
            assert c.metrics is not None
            assert c.metrics["decode_totals"]["total_energy_j"] > 0

    def test_server_dense_arch(self):
        cfg = get_config("smollm-360m").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        server = SliceMoEServer(cfg, params, engine_cfg=None, max_seq=64)
        server.submit(Request(request_id=0,
                              prompt=np.arange(16, dtype=np.int32),
                              max_new_tokens=4))
        done = server.run()
        assert len(done[0].tokens) == 4


class TestQuantizedDecodeFidelity:
    """AMAT decode must track the float model; naive low-bit-everything
    (lowbit mode) must be measurably worse than DBSC at equal cache."""

    @pytest.fixture(scope="class")
    def setup(self):
        cfg = get_config("qwen15-moe-repro")
        cfg = dataclasses.replace(cfg, n_layers=2)
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0,
                                  cfg.vocab_size)
        from repro.models.model import prefill
        logits_oracle, _, _ = prefill(params, cfg, toks, max_seq=64)
        return cfg, params, toks, logits_oracle

    def _engine_logits(self, cfg, params, toks, slice_mode, theta=0.5):
        eng = SliceMoEEngine(cfg, params, EngineConfig(
            mat=MatConfig(8, 4), cache_bytes=50e6,   # everything fits
            policy=RoutingPolicy(kind="topk", slice_mode=slice_mode,
                                 theta=theta),
            warmup="pcw", max_seq=64))
        return np.asarray(eng.prefill(toks)), eng

    def test_highbit_engine_close_to_float(self, setup):
        cfg, params, toks, oracle = setup
        logits, _ = self._engine_logits(cfg, params, toks, "highbit")
        top_f = np.argsort(np.asarray(oracle)[0])[-5:]
        top_q = np.argsort(logits[0])[-5:]
        assert len(set(top_f) & set(top_q)) >= 3

    def test_dbsc_decode_vs_lowbit_decode(self, setup):
        """DBSC (critical experts high-bit) should be at least as close to
        the high-bit decode as uniformly-low-bit decode is."""
        cfg, params, toks, _ = setup

        def decode_logits(slice_mode):
            eng = SliceMoEEngine(cfg, params, EngineConfig(
                mat=MatConfig(8, 2),      # aggressive low bits: 2b
                cache_bytes=50e6,
                policy=RoutingPolicy(kind="topk", slice_mode=slice_mode,
                                     theta=0.3),
                warmup="pcw", max_seq=64))
            logits = eng.prefill(toks)
            first = jnp.argmax(logits, -1).astype(jnp.int32)
            ps = eng._policy_state()
            out, eng.kv_cache, _ = eng._jit_decode(
                eng.qparams, token=first, cache=eng.kv_cache,
                policy_state=ps, alpha=jnp.float32(0.0))
            return np.asarray(out)

        hi = decode_logits("highbit")
        db = decode_logits("dbsc")
        lo = decode_logits("lowbit")
        err_db = np.abs(db - hi).max()
        err_lo = np.abs(lo - hi).max()
        assert err_db <= err_lo + 1e-5, (err_db, err_lo)


class TestQuantExecutionParity:
    """Quantized execution (packed-code Pallas kernels) vs the
    dense-dequant reference path, end-to-end through the engine: at f32
    model dtype both jitted fns must agree to kernel-accumulation
    accuracy (1e-4)."""

    @pytest.fixture(scope="class")
    def setup(self):
        cfg = get_config("qwen15-moe-repro")
        cfg = dataclasses.replace(cfg, n_layers=2, dtype="float32")
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0,
                                  cfg.vocab_size)
        return cfg, params, toks

    def _run(self, setup, quant_execution: bool):
        cfg, params, toks = setup
        eng = SliceMoEEngine(cfg, params, EngineConfig(
            mat=MatConfig(8, 4), cache_bytes=50e6,
            policy=RoutingPolicy(kind="topk", slice_mode="dbsc",
                                 quant_execution=quant_execution),
            warmup="pcw", max_seq=48))
        prefill_logits = eng.prefill(toks)
        first = jnp.argmax(prefill_logits, -1).astype(jnp.int32)
        tokens, _ = eng.decode(first, 4)
        ps = eng._policy_state()
        decode_logits, _, _ = eng._jit_decode(
            eng.qparams, token=first, cache=eng.kv_cache,
            policy_state=ps, alpha=jnp.float32(0.0))
        return (np.asarray(prefill_logits), np.asarray(decode_logits),
                np.asarray(tokens), eng)

    def test_decode_logits_match_dense_path(self, setup):
        pre_d, dec_d, tok_d, _ = self._run(setup, False)
        pre_q, dec_q, tok_q, _ = self._run(setup, True)
        np.testing.assert_allclose(pre_q, pre_d, atol=1e-4)
        np.testing.assert_allclose(dec_q, dec_d, atol=1e-4)
        np.testing.assert_array_equal(tok_q, tok_d)

    def test_quant_execution_moves_fewer_weight_bytes(self, setup):
        """The point of the tentpole: packed-code execution must stream
        >= 2x fewer expert-weight HBM bytes than dense dequant."""
        *_, eng = self._run(setup, True)
        dense = eng.expert_weight_bytes_per_step(quant_execution=False)
        quant = eng.expert_weight_bytes_per_step(quant_execution=True)
        assert quant * 2 <= dense, (quant, dense)

    def test_engine_holds_one_copy_of_expert_weights(self, setup):
        """The packed codes in the param tree are the only expert
        weights: no float copy, no transposed copy, no store copy."""
        *_, eng = self._run(setup, True)
        assert not hasattr(eng, "float_params")
        for blk in eng.qparams["blocks"].values():
            if "moe" in blk:
                assert set(blk["moe"]["experts"]) == {"wi_q", "wo_q"}
        leaves = jax.tree_util.tree_leaves(dataclasses.astuple(eng.store))
        assert not any(isinstance(x, jax.Array) for x in leaves)


@pytest.mark.slow
class TestTrainSSMDonation:
    def test_train_loop_ssm_arch_donation_safe(self):
        """Regression: f32 SSM params (A_log/D/dt_bias) must not alias the
        f32 optimizer master copy — jit donation of (params, opt_state)
        fails with 'donate the same buffer twice' if they do."""
        cfg = get_config("mamba2-2.7b").reduced()
        _, _, hist = train_loop(cfg, steps=3, global_batch=2, seq_len=16,
                                opt_cfg=OPT.AdamWConfig(
                                    lr=1e-3, total_steps=3, warmup_steps=1),
                                log_every=1000, collect_history=True)
        assert np.isfinite(hist[-1]["loss"])
