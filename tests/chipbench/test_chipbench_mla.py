"""DeepSeek-V2-Lite's decoder family (``chipbench/families/mla_moe.py``):
its sizes from the configuration file, the weight layout the program
takes, the YaRN numbers of its reference, its work counts by hand, its
metric readers, and a whole run at a tiny size on the CPU: prefill, then
decode through the latent cache, held to the reference's expanded
forward, with the float8 control failing the same comparison."""

import importlib.util
import inspect
import os

import chipbench_tiny as tiny
import jax
import numpy as np
import pytest

from chipbench import run, tracereduce as T
from chipbench.cellconfig import dims, family, load_json
from chipbench.work import least_time, peaks, step_work

MLA = family("mla_moe")
METRICS = os.path.join(tiny.ROOT, "chipbench", "metrics")


def conf() -> dict:
    """The configuration file at a tiny size: the published structure
    (one leading dense layer, YaRN, raw gates), small widths."""
    c = load_json("configs", "deepseek-v2-lite.json")
    c.update(hidden_size=128, intermediate_size=128, moe_intermediate_size=64,
             n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
             num_attention_heads=4, num_key_value_heads=4,
             num_hidden_layers=3, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=16, v_head_dim=16, vocab_size=512)
    return c


def test_dims_of_the_configuration_file():
    dm = dims(load_json("configs", "deepseek-v2-lite.json"))
    assert dm == {
        "family": "mla_moe", "d": 2048, "layers": 5,
        "moe_layers": (1, 2, 3, 4), "dense_ff": 10944, "experts": 64,
        "top_k": 6, "norm_topk": False, "expert_ff": 1408, "shared_ff": 2816,
        "vocab": 102400, "eps": 1e-06, "high_bits": 8, "low_bits": 4,
        "group_size": 32, "theta": 0.5, "capacity_factor": 2.0,
        "heads": 16, "kv_rank": 512, "qk_nope": 128, "qk_rope": 64,
        "v_dim": 128, "rope_theta": 10000.0, "yarn_factor": 40.0,
        "yarn_original_max": 4096, "yarn_beta_fast": 32.0,
        "yarn_beta_slow": 1.0, "yarn_mscale": 0.707,
        "yarn_mscale_all_dim": 0.707}


@pytest.mark.parametrize("key, value", [("q_lora_rank", 1536),
                                        ("scoring_func", "sigmoid"),
                                        ("moe_layer_freq", 2)])
def test_what_the_program_does_not_serve_is_refused(key, value):
    with pytest.raises(ValueError):
        dims(dict(conf(), **{key: value}))


@pytest.mark.parametrize("make", [conf, lambda: load_json(
    "configs", "deepseek-v2-lite.json")], ids=["tiny", "published"])
def test_weight_layout_is_the_programs(make):
    from repro.models import model as MDL

    c = make()
    dm = dims(c)
    cfg = MLA.model_config(c, dm)
    assert MDL.param_shapes(cfg) == MLA.param_shapes(dm)
    assert cfg.n_periods == 4 if c["num_hidden_layers"] == 5 else 2
    assert cfg.moe.norm_topk is False and cfg.first_k_dense == 1


def test_reference_yarn_is_the_programs_and_the_hand_values():
    from repro.models import layers as L

    c = load_json("configs", "deepseek-v2-lite.json")
    dm = dims(c)
    cfg = MLA.model_config(c, dm)
    ref = np.asarray(MLA.inv_freq(dm))
    prog = np.asarray(L.yarn_inv_freq(64, 1e4, cfg.rope_scaling))
    np.testing.assert_allclose(ref, prog, rtol=1e-6)
    # By hand: lo = 10, hi = 23; halfway-ish at i = 16 (m = 7/13).
    assert ref[16] == pytest.approx(0.0055, rel=1e-5)
    assert MLA.softmax_scale(dm) == pytest.approx(0.1147214, rel=1e-6)
    assert MLA.softmax_scale(dm) == pytest.approx(
        L.mla_softmax_scale(192, cfg.rope_scaling))


def test_reference_imports_nothing_of_the_program_outside_model_config():
    src = inspect.getsource(MLA)
    body = inspect.getsource(MLA.model_config)
    assert "repro" not in src.replace(body, "")
    # The absorbed form is the program's alone.
    assert "absorb" not in inspect.getsource(MLA.attention)


# Work counts by hand at a tiny size: 2 layers (one dense, one MoE), d 8,
# 2 heads, rank 4, rope 2, nope 2, v 2; 3 experts, shared 4; vocab 10.
DM = {"family": "mla_moe", "d": 8, "layers": 2, "moe_layers": (1,),
      "dense_ff": 6, "experts": 3, "top_k": 1, "expert_ff": 32,
      "shared_ff": 4, "vocab": 10, "group_size": 32, "high_bits": 8,
      "low_bits": 4, "heads": 2, "kv_rank": 4, "qk_nope": 2, "qk_rope": 2,
      "v_dim": 2}


def test_mla_work_by_hand():
    nbytes, flops = MLA.mla_work(DM, [5, 2])
    # 7 rows of 6 bf16 values, 2 sequences x 2 heads of 6 bf16 queries
    # and 4 f32 outputs, in each of 2 layers.
    assert nbytes == 2 * (7 * 6 * 2 + 2 * 2 * (6 * 2 + 4 * 4))
    assert flops == 2 * 7 * 2 * 2 * (6 + 4)


def test_dense_work_by_hand():
    nbytes, flops = MLA.dense_work(DM, [5, 2])
    attn = 8 * 2 * 4 + 8 * 6 + 4 * 2 * 4 + 2 * 2 * 8   # wq wkv_a wkv_b wo
    weights = 2 * attn + (8 * 3 + 3 * 8 * 4) + 3 * 8 * 6
    norms = 2 * (2 * 8 + 4)
    assert nbytes == (weights + norms + 8 * 10 + 8 + 2 * 8) * 2 \
        + 2 * 7 * 6 * 2
    assert flops == 2 * 2 * weights + MLA.mla_work(DM, [5, 2])[1] \
        + 2 * 2 * 8 * 10


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _View:
    def __init__(self, trace):
        self.trace, self.dm, self.peaks = trace, DM, peaks("TPU v5 lite")
        self.steps = [{"kv_len": [5, 2], "t0": 0.0, "t1": 1e-6}] * 6
        ids = np.zeros((1, 1, 2, 1), np.int64)
        act = np.ones((1, 1, 2, 1), bool)
        self.plan = [(ids, act, act)] * 6
        self.prefills = [(0.0, 1.0, 1024)] * 3


def _trace():
    spans = [("decode_batch", 0, 100, {"n": 4}),
             ("run_prefill", 100, 200, {"n": 2}),
             ("decode_batch", 200, 300, {"n": 5})]
    modules = T.label_modules([("_unknown(1)", 10, 90),
                               ("_unknown(2)", 110, 190),
                               ("_unknown(1)", 210, 290)], spans)
    ops = T.name_ops(
        [("%fusion.1 = f32[8]{0} fusion()", 10, 20),
         ("%mla_decode_attention.3 = f32[2,2,4]{2,1,0} custom-call()",
          20, 30),
         ("%mla_decode_attention.3 = f32[2,2,4]{2,1,0} custom-call()",
          40, 45),
         ("%fusion.2 = f32[8]{0} fusion()", 120, 180),
         ("%mla_decode_attention.3 = f32[2,2,4]{2,1,0} custom-call()",
          220, 240)], modules)
    return T.Trace(ops, modules, spans, 0, 300)


def test_mla_roofline_reads_the_kernels_ops():
    view = _View(_trace())
    got = _reader("mla_roofline.decode")(view)
    need = 2 * least_time(*MLA.mla_work(DM, [5, 2]), view.peaks)
    assert got == pytest.approx(100.0 * need / ((15 + 20) / 1e9))
    # No kernel op in the trace (a program without it): nothing read.
    bare = _trace()
    bare.ops = [o for o in bare.ops if "mla_decode" not in o[0]]
    assert _reader("mla_roofline.decode")(_View(bare)) is None


def test_new_readers_read_as_the_accepted_ones():
    view = _View(_trace())
    assert _reader("prefill_ms_per_ktok.doc")(view) == \
        _reader("prefill_ms_per_ktok.tput")(view) == pytest.approx(80e-6)
    got = _reader("step_mfu.decode.mla")(view)
    assert got == _reader("step_mfu.decode")(view)
    need = least_time(*step_work(DM, *view.plan[0], [5, 2]), view.peaks)
    assert got == pytest.approx(100.0 * need / 1e-6)


def _run(control=False):
    return run.run_cell(tiny.WORKLOAD, conf(), tiny.mix(), tiny.SEED, 2.0,
                        False, tiny.bench(), require_tpu=False,
                        control=control)


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_sound_run_is_correct(sound):
    assert sound["correct"]
    assert sound["failed"] == 0 and sound["attempted"] == 12
    for name, c in sound["checks"].items():
        assert 0 <= c["value"] <= c["limit"], name


def test_float8_control_reads_far_above_the_program(sound):
    control = _run(control=True)
    assert not control["correct"]
    gap = control["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"] > \
        sound["checks"]["logit_gap_max"]["value"]


def test_cell_holds_a_latent_cache_and_a_dense_prefix():
    """The cell's weights carry the prefix beside the MoE layers, and its
    batch cache holds one latent row (rank 32 + rope 16 here, in 128
    lanes) a position and layer."""
    from chipbench import harness, weights
    from repro.models import model as MDL

    cell = harness.Cell(conf(), tiny.mix(), tiny.SEED)
    params = jax.eval_shape(lambda: weights.draw(cell.dm, 1))
    assert set(params["blocks"]) == {"prefix", "pos0"}
    cache = jax.eval_shape(lambda: MDL.init_cache(cell.cfg, 3, 256))
    assert {k: v["ckv"].shape for k, v in cache.items() if k != "pos"} == \
        {"pos0": (2, 3, 256, 128), "prefix": (1, 3, 256, 128)}
