"""The readers of the program's own wall-clock spans
(``chipbench/hostspans.py`` and the six metrics that use it), on
hand-built rings and traces, and the program's annotations on a real
profiler trace of the tiny cell."""

import glob
import os
import tempfile
from collections import deque
from types import SimpleNamespace

import chipbench_tiny as tiny
import pytest

from chipbench import harness, hostspans, tracereduce
from chipbench.run import read_metric
from chipbench.tracereduce import Trace
from repro.obs.hosttrace import HostTrace

READERS = ("dispatch_ms_per_step", "charge_fetch_ms_per_step",
           "charge_replay_ms_per_step", "prefill_host_ms_per_ktok",
           "gc_ms_per_s", "idle_unattributed_share")
MS = 1_000_000                 # ns


def _step(ht, t, policy, launch, fetch, replay):
    """One decode step at ``t`` ns: ``decode`` then ``charge``, each with
    its children, closed in the order the program closes them."""
    ht.record("decode.policy", t, t + policy, {})
    ht.record("decode.launch", t + policy, t + policy + launch, {})
    ht.record("decode", t, t + policy + launch, {})
    c = t + policy + launch + 2 * MS
    ht.record("charge.fetch", c, c + fetch, {})
    ht.record("charge.replay", c + fetch, c + fetch + replay, {})
    ht.record("charge", c, c + fetch + replay, {})


def _run(ht, t0_s=1.0, seconds=1.0, trace=None, steps=()):
    return SimpleNamespace(cell=SimpleNamespace(engine=SimpleNamespace(
        host_trace=ht)), t0=t0_s, seconds=seconds, trace=trace,
        steps=list(steps))


def _ring():
    """Two steps before the window (which starts at 1 s), three in it, a
    prefill of 2048 tokens and two collector pauses, one across the
    window's end (2 s)."""
    ht = HostTrace()
    for t in (0.2, 0.5):
        _step(ht, int(t * 1e9), 9 * MS, 9 * MS, 9 * MS, 9 * MS)
    for t in (1.1, 1.4, 1.7):
        _step(ht, int(t * 1e9), 1 * MS, 2 * MS, 3 * MS, 4 * MS)
    p = int(1.8e9)
    ht.record("prefill.charge", p, p + 5 * MS, {})
    ht.record("prefill.warmup", p + 5 * MS, p + 8 * MS, {})
    ht.record("prefill", p - MS, p + 9 * MS, {"tokens": 2048})
    ht.record("host.gc", int(1.2e9), int(1.2e9) + 6 * MS, {"generation": 0})
    ht.record("host.gc", int(2e9) - 2 * MS, int(2e9) + 8 * MS,
              {"generation": 2})
    return ht


def test_per_step_readers_sum_their_spans_over_decode_steps():
    run = _run(_ring())
    assert read_metric("dispatch_ms_per_step", run) == pytest.approx(3.0)
    assert read_metric("charge_fetch_ms_per_step", run) == pytest.approx(3.0)
    assert read_metric("charge_replay_ms_per_step", run) \
        == pytest.approx(4.0)


def test_prefill_host_time_per_thousand_prompt_tokens():
    run = _run(_ring())
    assert read_metric("prefill_host_ms_per_ktok", run) == pytest.approx(4.0)


def test_collector_time_is_clipped_to_the_window():
    run = _run(_ring())
    assert read_metric("gc_ms_per_s", run) == pytest.approx(8.0)
    quiet = HostTrace()
    _step(quiet, int(1.1e9), MS, MS, MS, MS)
    assert read_metric("gc_ms_per_s", _run(quiet)) == 0.0


def test_offset_is_the_median_over_traced_decode_batch_spans():
    steps = [{"t0": 1.0}, {"t0": 1.5}, {"t0": 2.0}]
    spans = [("decode_batch", 1e9 + 500, 0, {"n": 0}),
             ("decode_batch", 1.5e9 + 700, 0, {"n": 1}),
             ("decode_batch", 2e9 + 600, 0, {"n": 2}),
             ("run_prefill", 9, 10, {"n": 0})]
    run = _run(HostTrace(), trace=Trace([], [], spans), steps=steps)
    assert hostspans.offset_ns(run) == pytest.approx(600)


def test_innermost_span_takes_each_part_of_an_interval():
    spans = [("sched.step", 0, 100), ("sched.decode", 10, 90),
             ("decode", 10, 30), ("decode.policy", 10, 20),
             ("charge", 50, 80), ("host.gc", 60, 70)]
    split = hostspans.innermost_split([(0, 40), (45, 95), (120, 130)],
                                      spans)
    assert split == {"sched.step": 15, "decode.policy": 10, "decode": 10,
                     "sched.decode": 25, "charge": 20, "host.gc": 10,
                     "": 10}


def test_unattributed_idle_share_on_a_hand_built_trace():
    """Device busy 20-40 and 60-90 of a 0-100 scheduler step on the trace
    clock; the program's clock runs 1000 ns behind it.  Idle parts: 0-20
    (5 of it in ``sched.step`` alone, then ``decode.policy``), 40-60
    (``charge``) and 90-100 (``sched.step``)."""
    off = 1000
    ht = HostTrace()
    base = 10**9                     # program clock at the window start
    for name, s, e in (("decode.policy", 5, 20), ("decode", 5, 40),
                       ("charge", 40, 60), ("sched.step", 0, 100)):
        ht.record(name, base + s, base + e, {})
    T = base + off
    trace = Trace(ops=[("decode/fusion.1", T + 20, T + 40),
                       ("decode/fusion.2", T + 60, T + 90)],
                  modules=[],
                  spans=[("sched_step", T, T + 100, {}),
                         ("decode_batch", T + 5, T + 60, {"n": 0})],
                  t0=T, t1=T + 100)
    run = _run(ht, t0_s=1.0, trace=trace, steps=[{"t0": (base + 5) / 1e9}])
    split = hostspans.idle_by_span(run)
    assert split == pytest.approx({"sched.step": 15, "decode.policy": 15,
                                   "charge": 20}, abs=1e-3)
    assert read_metric("idle_unattributed_share", run) \
        == pytest.approx(100 * 15 / 50, abs=1e-3)


@pytest.mark.parametrize("name", READERS)
def test_readers_give_none_when_the_ring_wrapped(name):
    ht = HostTrace()
    ht.ring = deque(maxlen=4)        # a small ring, to wrap it cheaply
    for t in (1.1, 1.4):
        _step(ht, int(t * 1e9), MS, MS, MS, MS)
    run = _run(ht, trace=Trace([("decode/f", 0, 1)], [],
                               [("decode_batch", 0, 1, {"n": 0})]),
               steps=[{"t0": 1.1}])
    assert ht.dropped and read_metric(name, run) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_give_none_for_a_program_without_host_spans(name):
    run = _run(None, trace=Trace([("decode/f", 0, 1)], [],
                                 [("decode_batch", 0, 1, {"n": 0})]),
               steps=[{"t0": 0.0}])
    run.cell.engine = SimpleNamespace()
    assert read_metric(name, run) is None


@pytest.mark.slow
def test_annotations_land_on_their_ring_stamps():
    """A traced window of the benchmark's tiny cell: the program's own
    TraceAnnotations, mapped back through the harness-span offset, start
    within 0.1 ms of the ring's stamps."""
    from jax.profiler import ProfileData

    cell = harness.Cell(tiny.conf(), tiny.mix(), tiny.SEED)
    cell.setup()
    tdir = tempfile.mkdtemp(prefix="hosttrace-test-")
    t0 = cell.drive(cell.traffic.window(1.0), 1.0, drain_limit=60,
                    trace_at=(0.0, 60.0, tdir))
    tr = tracereduce.load(tdir)
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    names = {"decode", "decode.policy", "decode.launch", "charge",
             "charge.fetch", "charge.replay", "prefill", "sched.step"}
    got = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    got.setdefault(ev.name, []).append(ev.start_ns)

    class View:
        pass

    run = View()
    run.cell, run.t0, run.seconds, run.trace = cell, t0, 1.0, tr
    run.steps = cell.engine.steps
    off = hostspans.offset_ns(run)
    assert off is not None
    ring = [r for r in hostspans.window(run) if r[0] in names]
    assert len(ring) > 10 and set(got) == names
    # A stamp and its annotation are two clock reads a few instructions
    # apart; a busy host can preempt a rare one between them.
    err = sorted(min(abs(t - (s + off)) for t in got[name])
                 for name, s, _e, _st in ring)
    assert err[len(err) // 2] < 2e4 and err[int(0.95 * len(err))] < 1e5
