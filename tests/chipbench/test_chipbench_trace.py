"""The trace reduction: interval arithmetic, attribution of device work to
the harness spans that launched it, and a short trace recorded on a
TPU v5e (``chipbench/recorded/``).

The recorded trace is the ``*.xplane.pb`` that a ``--trace 1`` run of
``qwen15-moe-a2.7b.chat`` wrote (a traced part cut to a third of a
second), gzipped.  To record another, copy that file out of the run's
trace directory before ``run.serve`` removes it."""

import os

import chipbench_tiny  # noqa: F401
import pytest

from chipbench import tracereduce as T

REC = os.path.join(chipbench_tiny.ROOT, "chipbench", "recorded")


def test_interval_math():
    u = T.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert T.length(u) == 6
    assert T.intersect(u, [(2, 6)]) == [(2, 3), (5, 6)]
    assert T.gaps(u, [(0, 10)]) == [(3, 5), (8, 10)]


def _synthetic():
    spans = [("sched_step", 0, 100, {}), ("decode_batch", 10, 90, {"n": 4}),
             ("charge_decode_step", 60, 88, {}),
             ("sched_step", 100, 200, {}),
             ("run_prefill", 105, 150, {"n": 2}),
             ("decode_batch", 150, 195, {"n": 5})]
    # The prefill's execution shows up just before its span opens (the
    # device clock runs ahead); a short eager program runs inside a span.
    raw = [("_unknown(1)", 20, 55), ("_unknown(2)", 104, 140),
           ("_argmax(3)", 141, 142), ("_unknown(1)", 155, 185)]
    modules = T.label_modules(raw, spans)
    ops = T.name_ops([("%fusion.1 = f32[8]{0} fusion()", 20, 30),
                      ("%amat_expert_matmul.3 = f32[8]{0} custom-call()", 30, 50),
                      ("%fusion.2 = f32[8]{0} fusion()", 50, 55),
                      ("%amat_expert_matmul.3 = f32[8]{0} custom-call()", 115, 135),
                      ("%amat_expert_matmul.3 = f32[8]{0} custom-call()", 160, 170)],
                     modules)
    return T.Trace(ops, modules, spans, 0, 200)


def test_step_programs_are_known_by_their_launching_span():
    tr = _synthetic()
    assert [m[0] for m in tr.modules] == ["decode", "prefill", "_argmax",
                                          "decode"]
    dec = T.executions(tr, "decode")
    assert [(e["n"], e["end"] - e["start"], e["kernel_ns"]) for e in dec] \
        == [(4, 35, 20), (5, 30, 10)]
    pre = T.executions(tr, "prefill")
    assert [(e["n"], e["kernel_ns"]) for e in pre] == [(2, 20)]
    assert [o[0] for o in tr.ops][:2] == ["decode/fusion.1",
                                          "decode/amat_expert_matmul.3"]


def test_idle_is_labelled_by_the_host_span():
    tr = _synthetic()
    busy = T.busy(tr)
    assert T.length(busy) == 35 + 20 + 10
    assert T.label_of(tr, 70) == "charge path"
    assert T.label_of(tr, 12) == "decode_batch"
    assert T.label_of(tr, 145) == "admission/prefill"
    assert T.label_of(tr, 5) == "scheduler other"


def test_self_times_leave_out_nested_operations():
    ops = [("d/while.4", 0, 100), ("d/fusion.1", 0, 30),
           ("d/amat_expert_matmul.3", 40, 70), ("d/copy.2", 100, 110)]
    assert T.self_times(ops) == {"d/while.4": 40, "d/fusion.1": 30,
                                 "d/amat_expert_matmul.3": 30, "d/copy.2": 10}


def test_ops_are_named_by_their_own_instruction():
    # Event names as the TPU's XLA Ops line gives them (HLO text).
    kern = ("%amat_expert_matmul.17 = f32[64,8,2048]{2,1,0:T(8,128)S(1)} "
            "custom-call(s32[64]{0:T(128)S(1)} %convert_element_type.481, "
            "bf16[64,8,1408]{2,1,0} %fusion.242), "
            "custom_call_target=\"tpu_custom_call\"")
    user = ("%fusion.9 = bf16[16,2048]{1,0} fusion(f32[64,8,2048]{2,1,0} "
            "%amat_expert_matmul.17), kind=kLoop")
    assert T.op_name(kern) == "amat_expert_matmul.17"
    assert T.op_name(user) == "fusion.9"
    assert T.program_name("jit_decode_step(12)") == "decode_step"
    spans = [("decode_batch", 0, 52, {"n": 0})]
    modules = T.label_modules([("_unknown(12)", 0, 50)], spans)
    tr = T.Trace(T.name_ops([(kern, 10, 20), (user, 20, 25)], modules),
                 modules, spans, 0, 52)
    # The fusion that reads the kernel's output is not kernel time.
    assert [e["kernel_ns"] for e in T.executions(tr, "decode")] == [10]


@pytest.fixture(scope="module")
def recorded():
    return T.load(os.path.join(REC, "qwen15-moe-a2.7b.chat.xplane.pb.gz"))


def test_recorded_trace(recorded):
    """0.33 s of the Qwen chat cell's window on a TPU v5e (batch 16)."""
    tr = recorded
    dec = T.executions(tr, "decode")
    assert [e["n"] for e in dec] == list(range(95, 105))
    for e in dec:
        ms = (e["end"] - e["start"]) / 1e6
        assert 15 < ms < 25 and 0 < e["kernel_ns"] < e["end"] - e["start"]
    pre = T.executions(tr, "prefill")
    assert [e["n"] for e in pre] == [8] and pre[0]["kernel_ns"] > 0
    kern = {o[0] for o in tr.ops if "amat_expert_matmul" in o[0]}
    assert kern == {"decode/amat_expert_matmul.16",
                    "decode/amat_expert_matmul.17",
                    "prefill/amat_expert_matmul.16",
                    "prefill/amat_expert_matmul.17"}
    # Nested operations are counted once: self times add up to busy time.
    busy = T.length(T.busy(tr))
    assert sum(T.self_times(tr.ops).values()) == pytest.approx(busy)
    assert 0 < busy < tr.t1 - tr.t0
    labels = {T.label_of(tr, (s + e) / 2)
              for s, e in T.gaps(T.busy(tr), [(tr.t0, tr.t1)])}
    assert labels <= {"charge path", "decode_batch", "admission/prefill",
                      "scheduler other", "generator wait", "outside spans"}
