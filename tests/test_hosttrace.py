"""Wall-clock host spans and counters (repro.obs.hosttrace): the span
facility itself, the spans the scheduler and engine open on a tiny served
run, the request wall stamps, the metrics fold and the named step
programs."""

import dataclasses
import gc
import re
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.amat import MatConfig
from repro.core.engine import (EngineConfig, PersistentEngine,
                               SliceMoEEngine)
from repro.core.slices import ExpertSliceStore
from repro.models import model as MDL
from repro.models.moe import RoutingPolicy
from repro.obs.hosttrace import RING_SPANS, HostTrace
from repro.obs.metrics import MetricsRegistry
from repro.serving.scheduler import (ContinuousBatchingScheduler, Request,
                                     SchedulerConfig)

# --------------------------------------------------------- the facility


def test_spans_nest_and_name_the_innermost():
    ht = HostTrace()
    assert ht.innermost() == ""
    with ht.span("outer", n=1):
        assert ht.innermost() == "outer"
        with ht.span("inner"):
            assert ht.stack == ["outer", "inner"]
        assert ht.innermost() == "outer"
    assert ht.stack == []
    inner, outer = [r for r in ht.ring if r[0] != "host.gc"]
    assert (inner[0], outer[0], outer[3]) == ("inner", "outer", {"n": 1})
    assert outer[1] <= inner[1] <= inner[2] <= outer[2]


def test_records_without_stats_leave_the_collectors_lists():
    """A ring record of plain numbers is untracked at the next young
    collection, so a filling ring adds nothing to the long-lived count
    that brings on full collections."""
    ht = HostTrace()
    for _ in range(50):
        with ht.span("plain"):
            pass
    with ht.span("with.stats", n=1):
        pass
    gc.collect(0)
    ring = [r for r in ht.ring if r[0] != "host.gc"]
    assert [r[3] for r in ring[-2:]] == [None, {"n": 1}]
    assert not any(gc.is_tracked(r) for r in ring[:-1])


def test_span_closes_on_an_exception():
    ht = HostTrace()
    with pytest.raises(ValueError):
        with ht.span("boom"):
            raise ValueError
    assert ht.stack == [] and ht.counts["boom"] == 1


def test_ring_is_bounded_and_totals_match_it():
    gc.disable()                   # no collector pause in the ring here
    try:
        _ring_is_bounded(HostTrace())
    finally:
        gc.enable()


def _ring_is_bounded(ht):
    assert ht.ring.maxlen == RING_SPANS
    ht.ring = deque(maxlen=8)        # a small ring, to wrap it cheaply
    for i in range(5):
        ht.record("a" if i % 2 else "b", 100 * i, 100 * i + 10 + i, {})
    for name in ("a", "b"):
        ring = [r[2] - r[1] for r in ht.ring if r[0] == name]
        assert ht.counts[name] == len(ring)
        assert ht.seconds[name] == pytest.approx(1e-9 * sum(ring))
    assert ht.dropped == 0 and not ht.wrapped_since(0)
    for i in range(5, 20):
        ht.record("a", 100 * i, 100 * i + 10, {})
    assert len(ht.ring) == 8 and ht.dropped == 12
    assert ht.counts["a"] + ht.counts["b"] == 20
    # Records closing before the oldest kept one were lost; later not.
    assert ht.wrapped_since(0)
    assert not ht.wrapped_since(ht.ring[0][2] + 1)


def test_collector_pause_is_a_host_gc_span():
    ht = HostTrace()
    n, s = ht.counts["host.gc"], ht.gc_seconds[2]
    gc.collect()
    assert ht.counts["host.gc"] == n + 1
    name, t0, t1, stats = [r for r in ht.ring if r[0] == "host.gc"][-1]
    assert stats == {"generation": 2} and t1 >= t0
    assert ht.gc_seconds[2] == pytest.approx(s + 1e-9 * (t1 - t0))


def test_compile_is_filed_under_the_innermost_span():
    ht = HostTrace()
    scale = float(np.random.default_rng().integers(1, 1 << 30))

    def fresh(x):                  # a new function: always compiles
        return x * scale + 1.0

    with ht.span("outer"):
        with ht.span("step.that.compiles"):
            jax.block_until_ready(jax.jit(fresh)(jnp.ones(3)))
    assert ht.compiles["step.that.compiles"] >= 1
    assert ht.compile_seconds["step.that.compiles"] > 0
    assert ht.compiles["outer"] == 0


# --------------------------------------------------- a tiny served run


@pytest.fixture(scope="module")
def tiny():
    """A two-layer model a few dozen wide, with quantized execution."""
    base = get_config("qwen15-moe-repro")
    cfg = dataclasses.replace(
        base, n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
        vocab_size=512,
        moe=dataclasses.replace(base.moe, n_experts=8, top_k=2, d_ff=64,
                                n_shared_experts=1, d_ff_shared=128))
    mat = MatConfig(8, 4, group_size=32)
    ecfg = EngineConfig(
        mat=mat, cache_bytes=0.3 * ExpertSliceStore.for_config(
            cfg, mat).total_bytes(),
        policy=RoutingPolicy(kind="cache_prior", slice_mode="dbsc",
                             theta=0.5, quant_execution=True),
        miss_rate_target=0.05, warmup="pcw", max_seq=64)
    return cfg, MDL.init_params(cfg, jax.random.PRNGKey(0)), ecfg


@pytest.fixture(scope="module")
def served(tiny):
    """Four prompts through the scheduler (three slots, so one request
    waits for a slot)."""
    eng = PersistentEngine(*tiny)
    sched = ContinuousBatchingScheduler(eng, SchedulerConfig(max_batch=3))
    reg = sched.attach_metrics(MetricsRegistry())
    rng = np.random.default_rng(0)
    for i in range(4):
        assert sched.submit(Request(i, rng.integers(0, 512, 32).astype(
            np.int32), max_new_tokens=3 + i))
    sched.run()
    return eng, sched, reg


def _children(ring, parent):
    """Names of the spans inside ``parent`` (a ring record), by start;
    collector pauses left out."""
    return [r[0] for r in sorted(ring, key=lambda r: (r[1], -r[2]))
            if r is not parent and r[0] != "host.gc"
            and parent[1] <= r[1] and r[2] <= parent[2]]


@pytest.mark.slow
def test_every_decode_step_has_its_dispatch_and_charge_spans(served):
    eng, _, reg = served
    ring = list(eng.host_trace.ring)
    steps = [r for r in ring if r[0] == "sched.decode"]
    assert len(steps) == reg.series[-1]["decode_steps_total"] > 0
    for st in steps:
        inside = _children(ring, st)
        assert inside.count("decode") == inside.count("charge") == 1
        dec = next(r for r in ring if r[0] == "decode" and st[1] <= r[1]
                   and r[2] <= st[2])
        chg = next(r for r in ring if r[0] == "charge" and st[1] <= r[1]
                   and r[2] <= st[2])
        assert dec[2] <= chg[1]
        assert _children(ring, dec) == ["decode.policy", "decode.launch"]
        assert _children(ring, chg) == ["charge.fetch", "charge.migrate",
                                        "charge.replay"]
        assert "sched.sample" in inside


@pytest.mark.slow
def test_single_sequence_decode_opens_the_same_spans(tiny):
    """``SliceMoEEngine.decode`` (the server's cold path) steps through
    ``decode_batch``, so each of its steps is spanned like a batch's."""
    eng = SliceMoEEngine(*tiny)
    logits = eng.prefill(jnp.arange(32, dtype=jnp.int32)[None] % 512)
    eng.decode(jnp.argmax(logits, -1).astype(jnp.int32), 3)
    ring = [r for r in eng.host_trace.ring if r[0] != "host.gc"]
    assert eng.host_trace.counts["decode"] \
        == eng.host_trace.counts["charge"] == 3
    for dec in (r for r in ring if r[0] == "decode"):
        assert _children(ring, dec) == ["decode.policy", "decode.launch"]


@pytest.mark.slow
def test_every_admission_has_its_prefill_spans(served):
    eng, _, _ = served
    ring = list(eng.host_trace.ring)
    admits = [r for r in ring if r[0] == "sched.admit"]
    assert [r[3]["request"] for r in admits] == [0, 1, 2, 3]
    for ad in admits:
        inside = _children(ring, ad)
        assert inside == ["prefill", "prefill.launch", "prefill.fetch",
                          "prefill.charge", "prefill.warmup",
                          "sched.install"]
    assert [r[3]["tokens"] for r in ring if r[0] == "prefill"] == [32] * 4
    retires = [r for r in ring if r[0] == "sched.retire"]
    assert len(retires) == 4


@pytest.mark.slow
def test_request_wall_stamps_give_the_completion_times(served):
    _, sched, _ = served
    for c in sched.completions:
        rec = sched.telemetry.requests[c.request_id]
        assert 0 < rec.submit_wall_ns <= rec.admit_wall_ns \
            < rec.first_token_wall_ns < rec.finish_wall_ns
        assert c.prefill_s == pytest.approx(
            1e-9 * (rec.first_token_wall_ns - rec.admit_wall_ns))
        assert c.decode_s == pytest.approx(
            1e-9 * (rec.finish_wall_ns - rec.first_token_wall_ns))
    # The request that waited for a slot was admitted after the others.
    recs = sched.telemetry.requests
    assert recs[3].admit_wall_ns > max(recs[i].admit_wall_ns
                                       for i in range(3))


@pytest.mark.slow
def test_metrics_registry_carries_the_host_totals(served):
    eng, _, reg = served
    ht = eng.host_trace
    row = reg.series[-1]      # sampled at the last decode step
    assert row['host_span_count_total{span="decode"}'] == ht.counts["decode"]
    assert 0 < row['host_span_seconds_total{span="charge.replay"}'] \
        <= ht.seconds["charge.replay"]
    for key, n in ((f'jit_compiles_total{{span="{k}"}}', v)
                   for k, v in ht.compiles.items()):
        assert row.get(key, 0) <= n
    for key, v in ((f'host_gc_seconds_total{{generation="{g}"}}', v)
                   for g, v in ht.gc_seconds.items()):
        assert row.get(key, 0) <= v
    txt = reg.prometheus_text()
    for name in ("host_span_seconds_total", "host_span_count_total"):
        assert f"# TYPE {name} counter" in txt


@pytest.mark.slow
def test_step_programs_are_named_and_scoped(served):
    eng, sched, _ = served
    b = sched.cfg.max_batch
    low = eng._jit_decode.lower(
        eng.qparams, token=jnp.zeros(b, jnp.int32), cache=sched.batch_cache,
        policy_state=eng._policy_state(), alpha=jnp.float32(0.0),
        token_mask=jnp.ones(b, bool))
    hlo = low.as_text(dialect="hlo", debug_info=True)
    assert hlo.startswith("HloModule jit_decode_step")
    for scope in ("attn", "moe", "experts", "unembed"):
        assert re.search(f'op_name="([^"]*/)?{scope}/', hlo), scope
    pre = eng._jit_prefill.lower(eng.qparams,
                                 tokens=jnp.zeros((1, 32), jnp.int32))
    assert pre.as_text(dialect="hlo").startswith("HloModule jit_prefill")
