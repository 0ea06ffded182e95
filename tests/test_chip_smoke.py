"""The chip bring-up script's serving function, at the tiny repro size on
CPU (Pallas kernels in interpret mode), so the script cannot rot between
chip runs."""

import dataclasses
import importlib.util
import os

import jax
import pytest

from repro.configs.base import get_config

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serves_tiny_config_and_matches_dense_path(chip_smoke):
    cfg = dataclasses.replace(get_config("qwen15-moe-repro"), n_layers=2)
    r = chip_smoke.serve(cfg, n_requests=3, prompt_len=16, max_new=3,
                         max_batch=2)
    assert r["n_completed"] == 3
    assert r["n_logits_checked"] > 0
    assert r["logit_rel_err"] <= chip_smoke.LOGIT_REL_TOL
    assert 0 < r["cache_bytes"] < r["store_bytes"]
    assert r["compile_s"] > 0 and r["decode_step_s"] > 0


def test_published_widths(chip_smoke):
    cfg = chip_smoke.qwen15_moe_a27b(chip_smoke.N_LAYERS)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) \
        == (2048, 16, 16, 128)
    assert (cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.d_ff,
            cfg.moe.d_ff_shared) == (60, 4, 1408, 5632)
    assert (cfg.vocab_size, cfg.qkv_bias, cfg.tie_embeddings) \
        == (151936, True, False)
    assert cfg.n_layers >= 4


def test_refuses_to_run_without_a_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out
