"""What the benchmark reads for the existing cells, pinned: the sizes of
both configuration files, the seeded weights (bit for bit), the work
counts and the reference's logits.  Every expected value here was
computed by the harness before the decoder family was split out of it
(``chipbench/families/mha_moe.py``); ``family``, ``moe_layers`` and
``norm_topk`` are the keys the split added to the sizes."""

import hashlib

import chipbench_tiny as tiny
import jax
import numpy as np
import pytest

from chipbench import weights
from chipbench.cellconfig import dims, load_json
from chipbench.reference import Reference, sequence_plan
from chipbench.work import expert_work, step_work
from test_chipbench_work import DM, _step

ADDED = {"family": "mha_moe", "moe_layers": (0, 1, 2, 3), "norm_topk": True}
DIMS = {
    "qwen15-moe-a2.7b": {
        "d": 2048, "heads": 16, "kv_heads": 16, "head_dim": 128, "layers": 4,
        "dense_ff": 5632, "experts": 60, "top_k": 4, "expert_ff": 1408,
        "shared_ff": 5632, "vocab": 151936, "rope_theta": 1000000.0,
        "eps": 1e-06, "qkv_bias": True, "high_bits": 8, "low_bits": 4,
        "group_size": 32, "theta": 0.5, "capacity_factor": 2.0},
    "deepseek-moe-16b": {
        "d": 2048, "heads": 16, "kv_heads": 16, "head_dim": 128, "layers": 4,
        "dense_ff": 10944, "experts": 64, "top_k": 6, "expert_ff": 1408,
        "shared_ff": 2816, "vocab": 102400, "rope_theta": 10000.0,
        "eps": 1e-06, "qkv_bias": False, "high_bits": 8, "low_bits": 4,
        "group_size": 32, "theta": 0.5, "capacity_factor": 2.0},
}


@pytest.mark.parametrize("name", sorted(DIMS))
def test_dims_of_the_configuration_files(name):
    got = dims(load_json("configs", name + ".json"))
    assert got == dict(DIMS[name], **ADDED)


def test_weights_bit_for_bit():
    w = weights.draw(dims(tiny.conf()), 2**33 + 1)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == \
        "fbd1e35764b2b0a8af80f28076aee81bfba5a1c87b04b34c313ad842a402abe5"


def test_work_counts_exact():
    ids, active, critical = _step()
    assert expert_work(DM, ids, active, critical) == (15616.0, 49152.0)
    assert step_work(DM, ids, active, critical, [10, 3]) == \
        (72192.0, 156928.0)


def test_reference_logits():
    dm = dims(tiny.conf())
    rng = np.random.default_rng(7)
    prefill, steps = tiny.plan(dm, rng, dm["layers"])
    *plan, bad = sequence_plan(dm, prefill, steps, 3)
    assert bad == 0
    prompt = rng.integers(0, dm["vocab"], 6).astype(np.int32)
    served = rng.integers(0, dm["vocab"], 3).astype(np.int32)
    assert prompt.tolist() == [303, 57, 382, 309, 465, 245]
    assert served.tolist() == [88, 304, 233]
    lg, route, msb = Reference(dm, tiny.SEED, 16, 4)._run(
        prompt, served, plan, lowp=False)
    lg = np.asarray(lg)[:3]
    want = [[0.5809049010276794, -1.0984337329864502, 0.7987945675849915,
             0.49269089102745056, 0.9616789221763611, 2.172139883041382],
            [0.6830536127090454, -0.8542559742927551, 1.260579228401184,
             0.6070413589477539, -0.20999936759471893, 2.5813848972320557],
            [3.0202817916870117, -1.3996732234954834, 0.020991748198866844,
             -0.896589994430542, -0.22625046968460083, 1.876622200012207]]
    np.testing.assert_allclose(lg[:, :6], want, rtol=1e-6, atol=0)
    np.testing.assert_allclose(
        np.linalg.norm(lg, axis=-1),
        [27.63124656677246, 26.424001693725586, 25.37579345703125],
        rtol=1e-6, atol=0)
    assert lg.argmax(-1).tolist() == [356, 395, 37]
    assert route == pytest.approx(0.985072135925293, rel=1e-6)
    assert msb == pytest.approx(0.9091373682022095, rel=1e-6)
