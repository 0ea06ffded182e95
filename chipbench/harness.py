"""Drive one cell: set up the served path, warm it, and time a window.

The window drives ``ContinuousBatchingScheduler.step()`` over
:class:`BenchEngine`, a ``PersistentEngine`` that records wall-clock
spans around the engine's public calls.  The harness owns the arrival
schedule: it submits each request when it falls due, in wall time, with
``arrival_time=0.0``, so the scheduler's modelled clock never delays an
admission.  Token times are host-clock times at which the scheduler
holds the token: the first token at the end of ``run_prefill`` (the
prefill's argmax), every later one at the end of the ``step()`` whose
decode produced it.
"""

from __future__ import annotations

import dataclasses
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

import jax
import numpy as np
from jax import monitoring

from repro.core.amat import MatConfig
from repro.core.engine import EngineConfig, PersistentEngine
from repro.core.slices import ExpertSliceStore
from repro.models.moe import RoutingPolicy
from repro.serving.scheduler import (ContinuousBatchingScheduler, Request,
                                     SchedulerConfig)

from chipbench import weights
from chipbench.cellconfig import dims, family
from chipbench.traffic import Traffic, percentile

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
clock = time.perf_counter


def engine_config(conf: dict, cfg, max_seq: int) -> EngineConfig:
    eng = conf["engine"]
    mat = MatConfig(*eng["mat_bits"], group_size=eng["group_size"])
    store = ExpertSliceStore.for_config(cfg, mat)
    return EngineConfig(
        mat=mat, cache_bytes=eng["cache_share"] * store.total_bytes(),
        policy=RoutingPolicy(kind=eng["routing"], slice_mode=eng["slice_mode"],
                             theta=eng["criticality_theta"],
                             quant_execution=eng["quant_execution"]),
        miss_rate_target=eng["miss_rate_target"], warmup=eng["warmup"],
        max_seq=max_seq)


class Spans:
    """Wall-clock spans, and profiler annotations while tracing."""

    def __init__(self):
        self.annotate = False
        self.log: List[tuple] = []     # (name, t0, t1)

    @contextmanager
    def span(self, name: str, **stats):
        t0 = clock()
        if self.annotate:
            with jax.profiler.TraceAnnotation("cb." + name, **stats):
                yield
        else:
            yield
        self.log.append((name, t0, clock()))


class PlanRecorder:
    """Keeps the routing the engine hands its recorder hook: each
    prefill's ``ids`` by request, and each decode step's ``ids``,
    ``active`` and ``critical`` (the ``DecodeEvent`` arrays)."""

    def __init__(self):
        self.prefill: Dict[int, np.ndarray] = {}
        self.order: List[int] = []        # request id of each prefill
        self.decode: List[tuple] = []
        self._last = None

    def on_prefill(self, ids, gates, *, active=None, label=None,
                   inflight=0, tenant="default"):
        self._last = np.asarray(ids)

    def annotate_prefill(self, *, request_id=None, tenant=None):
        self.prefill[int(request_id)] = self._last
        self.order.append(int(request_id))

    def on_decode(self, tr):
        self.decode.append((tr.ids, tr.active, tr.critical))


class BenchEngine(PersistentEngine):
    """A ``PersistentEngine`` whose public calls are timed.

    ``run_prefill`` waits for its logits (the scheduler reads them next);
    ``charge_decode_step`` is timed after the step's outputs are ready,
    so its span holds host work only.  Each decode step also records
    which request sat in each slot and its KV length, from the
    scheduler's own slot table, and the routing inputs the policy read:
    ``alpha`` and the resident experts of each MoE pattern position.
    """

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.spans = Spans()
        self.sched: Optional[ContinuousBatchingScheduler] = None
        self.prefills: List[tuple] = []   # (t0, t1, prompt_len)
        self.steps: List[dict] = []
        self.first_token: Dict[int, int] = {}

    def run_prefill(self, tokens, **kw):
        t0 = clock()
        with self.spans.span("run_prefill", n=len(self.prefills)):
            out = super().run_prefill(tokens, **kw)
            jax.block_until_ready(out[0])
        self.prefills.append((t0, clock(), int(tokens.shape[1])))
        return out

    def _policy_state(self):
        self._state = super()._policy_state()
        return self._state

    def decode_batch(self, token, kv_cache, **kw):
        rids, kv_len = [], []
        for seq in self.sched.slots:
            if seq is None:
                rids.append(None)
                continue
            rid = seq.request.request_id
            rids.append(rid)
            if not seq.generated:
                self.first_token[rid] = int(seq.last_token)
            kv_len.append(len(seq.request.prompt) + len(seq.generated) + 1)
        rec = {"rids": rids, "kv_len": kv_len}
        rec["t0"] = clock()
        with self.spans.span("decode_batch", n=len(self.steps)):
            out = super().decode_batch(token, kv_cache, **kw)
        rec["t1"] = clock()
        rec["alpha"] = float(kw.get("alpha", 0.0))
        rec["resident"] = {k: v["cached_msb"]                # [periods, E]
                           for k, v in self._state.items()}
        self.steps.append(rec)
        return out

    def charge_decode_step(self, aux, slot_active=None, slot_tenants=None):
        jax.block_until_ready(aux)
        with self.spans.span("charge_decode_step"):
            return super().charge_decode_step(aux, slot_active=slot_active,
                                              slot_tenants=slot_tenants)


@dataclasses.dataclass
class ReqRecord:
    rid: int
    due: float                     # window-relative seconds
    prompt_len: int
    max_new: int
    submitted: Optional[float] = None
    prefill_start: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    rejected: bool = False


class Cell:
    """One cell's served path, built from its files and a seed."""

    def __init__(self, conf: dict, mix: dict, seed: int):
        self.conf, self.mix, self.seed = conf, mix, seed
        self.dm = dims(conf)
        self.cfg = family(self.dm["family"]).model_config(conf, self.dm)
        self.ecfg = engine_config(conf, self.cfg, mix["max_seq"])
        self.traffic = Traffic(mix, self.dm["vocab"], seed)
        self.n_compiles = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name == COMPILE_EVENT:
            self.n_compiles += 1

    # ---------------------------------------------------------- set-up
    def setup(self) -> None:
        params = weights.draw(self.dm, self.seed)
        self.engine = BenchEngine(self.cfg, params, self.ecfg)
        del params
        jax.block_until_ready(self.engine.qparams)
        self.recorder = PlanRecorder()
        self.engine.recorder = self.recorder
        self.sched = ContinuousBatchingScheduler(
            self.engine, SchedulerConfig(max_batch=self.mix["max_batch"],
                                         max_queue=1 << 20))
        self.engine.sched = self.sched
        self._next_rid = 0
        self.records: Dict[int, ReqRecord] = {}
        self.prompts: Dict[int, np.ndarray] = {}
        self.lateness: List[float] = []
        # Warm every prefill bucket and every slot, then let the slice
        # cache settle: all of this is set-up.
        self.drive(self.traffic.warm(), None, drain_limit=None)
        self.reset_records()

    def reset_records(self) -> None:
        e = self.engine
        e.prefills.clear()
        e.steps.clear()
        e.spans.log.clear()
        e.first_token.clear()
        self.recorder.prefill.clear()
        self.recorder.order.clear()
        self.recorder.decode.clear()
        self.sched.completions.clear()
        self.records = {}
        self.prompts: Dict[int, np.ndarray] = {}
        self.lateness: List[float] = []

    # ---------------------------------------------------------- driving
    def _submit(self, r, due: float, t0: float) -> None:
        rid = self._next_rid
        self._next_rid += 1
        rec = ReqRecord(rid, due, len(r.prompt), r.max_new)
        rec.submitted = clock() - t0
        self.lateness.append(rec.submitted - due)
        ok = self.sched.submit(Request(request_id=rid, prompt=r.prompt,
                                       max_new_tokens=r.max_new,
                                       arrival_time=0.0))
        rec.rejected = not ok
        self.records[rid] = rec
        self.prompts[rid] = r.prompt

    def _step(self, t0: float) -> None:
        e = self.engine
        n_pf, n_st = len(e.prefills), len(e.steps)
        with e.spans.span("sched_step"):
            self.sched.step()
        t_end = clock() - t0
        for j in range(n_pf, len(e.prefills)):
            rec = self.records[self.recorder.order[j]]
            rec.prefill_start = e.prefills[j][0] - t0
            rec.times.append(e.prefills[j][1] - t0)
        for st in e.steps[n_st:]:
            for rid in st["rids"]:
                if rid is not None:
                    self.records[rid].times.append(t_end)

    def drive(self, reqs, seconds: Optional[float], *,
              drain_limit: Optional[float], trace_at=None) -> float:
        """Serve ``reqs`` (open loop at their due offsets) or, when
        ``reqs`` is None, saturated traffic for ``seconds``.  With
        ``trace_at=(start, stop, log_dir)`` the profiler records that
        part of the window.  Returns the window's start (host clock)."""
        sp = self.engine.spans
        t0 = clock()
        i = 0
        reqs = list(reqs) if reqs is not None else None
        window_rids = []
        tracing = None
        while True:
            now = clock() - t0
            if trace_at is not None:
                tracing = self._trace_tick(now, trace_at, tracing)
            if reqs is not None:
                while i < len(reqs) and reqs[i].due_s <= now:
                    window_rids.append(self._next_rid)
                    self._submit(reqs[i], reqs[i].due_s, t0)
                    i += 1
            elif now < seconds:
                while len(self.sched.queue) < self.mix["max_batch"]:
                    window_rids.append(self._next_rid)
                    self._submit(self.traffic.next_saturated(), now, t0)
            pending = (reqs is not None and i < len(reqs))
            busy = bool(self.sched.queue) or self.sched.n_active() > 0
            if not busy and not pending and \
                    (reqs is not None or now >= seconds):
                break
            if drain_limit is not None and seconds is not None and \
                    now > seconds + drain_limit:
                break
            if busy:
                self._step(t0)
            else:
                with sp.span("wait"):
                    time.sleep(max(0.0, min(reqs[i].due_s - now, 0.05)))
        if tracing:
            self._trace_tick(float("inf"), trace_at, tracing)
        self.window_rids = window_rids
        return t0

    def _trace_tick(self, now, trace_at, tracing):
        start, stop, log_dir = trace_at
        if tracing is None and now >= start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            self.engine.spans.annotate = True
            return True
        if tracing and now >= stop:
            self.engine.spans.annotate = False
            jax.profiler.stop_trace()
            return False
        return tracing

    # --------------------------------------------------------- results
    def keep_for_check(self) -> None:
        """Copy out what the comparison reads: the slot table, routing and
        Cache-Prior boost (``1 + alpha`` on resident experts) of every
        decode step, each prefill's routing, every request's first token
        and completion.  Routing arrays come as [MoE layer, 1, ...] and
        the boost as [MoE layer, E], in the program's flat MoE-layer
        order (period-major over the pattern's MoE positions)."""
        e = self.engine
        self.steps_log = list(e.steps)

        def flat(a):
            return np.asarray(a).reshape((-1, 1) + a.shape[2:])

        def boost(st):
            res = {k: np.asarray(v) for k, v in st["resident"].items()}
            out = np.zeros((e.n_moe_layers, e.n_experts), np.float32)
            for (pos, period), li in e.layer_map.items():
                out[li] = res[f"pos{pos}"][period]
            return 1.0 + st["alpha"] * out

        self.plan_log = [tuple(flat(a) for a in tr) + (boost(st),)
                         for tr, st in zip(self.recorder.decode,
                                           self.steps_log)]
        self.prefill_plan = {r: flat(a)
                             for r, a in self.recorder.prefill.items()}
        self.first_tokens = dict(self.engine.first_token)
        self.completions = {c.request_id: c.tokens
                            for c in self.sched.completions}

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""
        self.sched.batch_cache = None
        self.engine.qparams = None
        self.sched = None
        self.engine.sched = None


def end_to_end(cell: Cell, seconds: float) -> dict:
    """The window's end-to-end numbers, and its request accounting."""
    recs = [cell.records[r] for r in cell.window_rids]
    done = [r for r in recs if not r.rejected
            and len(r.times) == r.max_new + 1]
    ttft = [1e3 * (r.times[0] - r.due) for r in done]
    gaps = [1e3 * g for r in done for g in np.diff(r.times)]
    span = sum(r.times[-1] - r.times[0] for r in done)
    n_gaps = sum(len(r.times) - 1 for r in done)
    in_window = sum(1 for r in recs for t in r.times if 0.0 <= t <= seconds)
    out = {
        "attempted": len(recs),
        "failed": len(recs) - len(done),
        "ttft_p90_ms": percentile(ttft, 90) if ttft else None,
        "itl_p95_ms": percentile(gaps, 95) if gaps else None,
        "tpot_ms": 1e3 * span / n_gaps if n_gaps else None,
        "tokens_per_s": in_window / seconds,
        "n_done": len(done),
    }
    return out


def device_summary() -> dict:
    dev = jax.devices()[0]
    mem = dev.memory_stats() or {}
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": mem.get("peak_bytes_in_use")}
