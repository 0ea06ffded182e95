"""The benchmark's needed-work counts against hand counts at tiny sizes."""

import chipbench_tiny  # noqa: F401
import numpy as np
import pytest

from chipbench.work import expert_work, least_time, peaks, step_work

DM = {"family": "mha_moe", "d": 64, "expert_ff": 32, "experts": 4,
      "group_size": 32, "high_bits": 8, "low_bits": 4, "vocab": 100,
      "layers": 1, "heads": 2, "kv_heads": 2, "head_dim": 32,
      "shared_ff": 16}


def _step():
    # 1 layer, 3 slots, top-2; slot 2 is padding (id 4 = out of range).
    ids = np.array([[[[0, 1], [1, 2], [4, 4]]]])
    active = np.array([[[[1, 1], [1, 1], [0, 0]]]], bool)
    critical = np.array([[[[1, 0], [0, 0], [0, 0]]]], bool)
    return ids, active, critical


def test_expert_work_by_hand():
    nbytes, flops = expert_work(DM, *_step())
    n_w = 3 * 64 * 32                   # wi [64, 64] + wo [32, 64]
    n_g = n_w / 32
    # expert 0 critical -> 8 bits; experts 1, 2 routed, never critical -> 4.
    codes = n_w * 1 + 2 * n_w * 0.5
    meta = n_g * (2 + 1) + 2 * n_g * (2 + 0.5)
    acts = 4 * (64 + 64 + 32 + 64) * 2  # 4 routed pairs, bf16
    assert nbytes == pytest.approx(codes + meta + acts)
    assert flops == pytest.approx(2 * 4 * n_w)


def test_step_work_adds_dense_weights_kv_and_unembed():
    ids, active, critical = _step()
    eb, ef = expert_work(DM, ids, active, critical)
    kv_len = [10, 3]
    nbytes, flops = step_work(DM, ids, active, critical, kv_len)
    attn = 64 * 6 * 32 + 64 * 64         # wq, wk, wv + wo
    dense = attn + 64 * 4 + 3 * 64 * 16  # + router + shared
    per_layer = (dense + 2 * 64 + 6 * 32) * 2 + 13 * 2 * 2 * 32 * 2
    assert nbytes == pytest.approx(eb + per_layer + (64 * 100 + 64 + 2 * 64) * 2)
    assert flops == pytest.approx(ef + 2 * 2 * dense + 4 * 13 * 2 * 32
                                  + 2 * 2 * 64 * 100)


def test_least_time_is_the_larger_bound():
    pk = peaks("TPU v5 lite")
    assert least_time(819e9, 0.0, pk) == pytest.approx(1.0)
    assert least_time(0.0, 197e12, pk) == pytest.approx(1.0)
    assert least_time(819e9, 2 * 197e12, pk) == pytest.approx(2.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks("cpu")
