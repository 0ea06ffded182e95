"""Continuous-batching scheduler over a persistent SliceMoE engine.

Replaces the seed's one-request-at-a-time loop: requests are admitted
into a fixed pool of ``max_batch`` decode *slots*, prefills interleave
with batched decode steps over all active slots, and sequences retire
individually on EOS or their token budget (their slot is immediately
refillable).  The engine — and with it the slice cache, the hotness
tracker and the cost ledger — persists across every request the
scheduler serves, so steady-state traffic runs against a *warm* cache.

Scheduling loop (one ``step()``):

  1. **Admission** — while a slot is free and the queue's head has
     arrived (simulated clock), pop it, run its prefill against the warm
     cache, and scatter its KV cache into the free slot.  Queue depth is
     bounded by ``max_queue``; submissions beyond it are rejected.
  2. **Batched decode** — one jitted ``decode_step`` over all
     ``max_batch`` slots with per-sequence positions; padding slots are
     masked out of cost accounting.
  3. **Retirement** — per-sequence EOS / length check; finished slots
     free up for the next admission.

The simulated clock is the cost ledger's accumulated latency, so
admission timing, TTFT and throughput are deterministic functions of the
workload and the modeled hardware — not of host jit times.

Per-request state (KV slot, step count, miss-rate-controller ``alpha``)
lives in :class:`ActiveSeq`; the batched call uses the mean alpha of the
active sequences (slots share one routing boost per step, a deliberate
simplification documented in docs/serving.md).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, Deque, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.engine import PersistentEngine
from repro.obs.hosttrace import now_ns
from repro.serving.telemetry import (FleetTelemetry, RequestRecord,
                                     StepRecord)


@dataclasses.dataclass
class Request:
    request_id: int
    prompt: np.ndarray            # [S] int32
    max_new_tokens: int = 32
    eos_token: Optional[int] = None
    arrival_time: float = 0.0     # simulated seconds
    tenant: str = "default"


@dataclasses.dataclass
class Completion:
    request_id: int
    tokens: np.ndarray
    prefill_s: float              # wall seconds (host): admission -> token
    decode_s: float               # first token -> retirement
    metrics: Optional[dict] = None


@dataclasses.dataclass
class SchedulerConfig:
    max_batch: int = 4
    max_queue: int = 64
    # Truncate prompts down to a multiple of this many tokens (0 = exact
    # lengths).  Bounds the number of distinct prefill jit traces under
    # length-diverse workloads.  Setting this is itself explicit consent
    # to (up to bucket_prompts-1 tokens of) truncation — it applies to
    # admitted prompts regardless of `truncate_prompts`, and clipped
    # requests are flagged on telemetry either way.
    bucket_prompts: int = 0
    # Admit over-budget prompts by clipping them to the KV budget
    # (keeping the tail, recorded on telemetry as ``truncated``).  Off by
    # default: the output for a clipped request is not the output for
    # the full prompt, so silent truncation must be opted into —
    # otherwise admission rejects any request whose full token budget
    # (prompt + max_new_tokens) cannot fit under ``max_seq``.
    truncate_prompts: bool = False
    # Admission-control hook: called with the Request at submit time;
    # returning False rejects it (recorded on telemetry like any other
    # rejection).  When None and the engine carries an SLO controller
    # (EngineConfig.controller), the controller's admit_request is wired
    # in automatically — its throttle actuator needs a say in admission.
    admission_hook: Optional[Callable[["Request"], bool]] = None


@dataclasses.dataclass
class ActiveSeq:
    """Per-request state pinned to one decode slot."""

    slot: int
    request: Request
    record: RequestRecord
    controller: object                 # MissRateController | None
    alpha: float = 0.0
    last_token: int = 0
    generated: List[int] = dataclasses.field(default_factory=list)
    ledger_base: Optional[dict] = None # snapshot at decode start
    prefill_end_t: float = 0.0         # sim clock when prefill settled


class ContinuousBatchingScheduler:
    """Admission control + continuous batching over a PersistentEngine."""

    def __init__(self, engine: PersistentEngine,
                 cfg: Optional[SchedulerConfig] = None):
        self.engine = engine
        # Wall-clock spans go to the engine's host trace (one per engine).
        self.host_trace = engine.host_trace
        self.cfg = cfg or SchedulerConfig()
        if self.cfg.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.queue: Deque[Request] = deque()
        self.slots: List[Optional[ActiveSeq]] = \
            [None] * self.cfg.max_batch
        self.batch_cache = engine.init_batch_cache(self.cfg.max_batch)
        self.telemetry = FleetTelemetry()
        self.completions: List[Completion] = []
        self.sim_time = 0.0
        self._ledger_mark = engine.ledger.total_latency_s
        self._admission_hook = self.cfg.admission_hook
        ctl = getattr(engine, "slo_controller", None)
        if ctl is not None:
            # Close the loop: the controller reads live telemetry (TTFT,
            # step records) and, absent an explicit hook, gates admission.
            ctl.attach_telemetry(self.telemetry)
            if self._admission_hook is None:
                self._admission_hook = ctl.admit_request

    def attach_recorder(self, recorder):
        """Wire a :class:`repro.sim.trace.TraceRecorder` into the engine.

        The engine hooks capture the replayable routing arrays; the
        scheduler additionally annotates each prefill event with the
        request id and tenant (which only it knows), so offline replays
        can be segmented per request / per tenant.  Returns the recorder
        for chaining.
        """
        return recorder.attach(self.engine)

    def attach_metrics(self, registry):
        """Sample a :class:`repro.obs.metrics.MetricsRegistry` per decode
        step: registers a :class:`~repro.obs.metrics.MetricsSampler` as a
        telemetry listener (the same mechanism the SLO controller rides),
        folding each StepRecord plus engine-side state — cache occupancy,
        ledger traffic, prefetch outcomes, controller actuation — into
        one catalog.  Returns the registry for chaining."""
        from repro.obs.metrics import MetricsSampler
        self.telemetry.add_listener(MetricsSampler(registry, self.engine))
        return registry

    # --------------------------------------------------------------- intake
    def servable(self, req: Request) -> bool:
        """Whether the request's *full* token budget fits the KV budget.

        Gates on ``len(prompt) + max_new_tokens``, not just the decode
        budget — a long prompt admitted on ``max_new_tokens`` alone would
        overflow its KV slot (or be silently truncated, which changes the
        answer).  With ``truncate_prompts`` the prompt side is waived:
        admission clips it to the budget and flags the request.
        (``bucket_prompts`` rounding is a separate, explicit opt-in and
        still applies to admitted prompts.)
        """
        max_seq = self.engine.ecfg.max_seq
        if not 1 <= req.max_new_tokens < max_seq - 1:
            return False
        if self.cfg.truncate_prompts:
            return True
        return len(req.prompt) + req.max_new_tokens + 1 <= max_seq

    def submit(self, req: Request) -> bool:
        """Admission control: reject queue overflow and unservable sizes.

        Rejecting here (rather than raising mid-run) keeps one bad
        request from aborting every in-flight sequence.
        """
        record = RequestRecord(
            request_id=req.request_id,
            tenant=getattr(req, "tenant", "default"),
            prompt_len=len(req.prompt),
            arrival_t=getattr(req, "arrival_time", 0.0),
            submit_wall_ns=now_ns())
        if len(self.queue) >= self.cfg.max_queue or not self.servable(req):
            self.telemetry.on_reject(record)
            return False
        if self._admission_hook is not None \
                and not self._admission_hook(req):
            self.telemetry.on_reject(record)
            return False
        self.telemetry.on_submit(record)
        self.queue.append(req)
        return True

    # ---------------------------------------------------------------- clock
    def _advance_clock(self) -> float:
        """Fold new ledger latency into the simulated clock; return delta."""
        now = self.engine.ledger.total_latency_s
        delta = now - self._ledger_mark
        self._ledger_mark = now
        self.sim_time += delta
        return delta

    # ------------------------------------------------------------ admission
    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is None]

    def n_active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def _clip_prompt(self, req: Request) -> np.ndarray:
        """Fit the prompt under the KV budget (keeping its tail).

        Truncation is recorded on the request's telemetry record and in
        its completion metrics — the output for a clipped request is not
        the output for the full prompt.
        """
        prompt = np.asarray(req.prompt, np.int32)
        budget = self.engine.ecfg.max_seq - req.max_new_tokens - 1
        if budget < 1:
            raise ValueError(
                f"request {req.request_id}: max_new_tokens="
                f"{req.max_new_tokens} leaves no room for a prompt under "
                f"max_seq={self.engine.ecfg.max_seq}")
        if len(prompt) > budget:
            prompt = prompt[-budget:]
        q = self.cfg.bucket_prompts
        if q > 1 and len(prompt) > q:
            # Round down to a multiple of q, keeping the most recent
            # tokens (same tail-keep rule as the budget clip above).
            prompt = prompt[-(len(prompt) // q) * q:]
        if len(prompt) != len(req.prompt):
            self.telemetry.requests[req.request_id].truncated = True
        return prompt

    def _admit_one(self, req: Request, slot: int) -> None:
        record = self.telemetry.requests[req.request_id]
        record.admit_t = self.sim_time
        record.admit_wall_ns = now_ns()
        prompt = self._clip_prompt(req)
        # Per-request stats epochs are only meaningful when requests run
        # one at a time; under batching, concurrent sequences would bleed
        # into whichever epoch was opened last, mislabeling their misses.
        # Fleet-level numbers come from telemetry either way.
        label = f"req{req.request_id}" if self.cfg.max_batch == 1 else None
        logits, kv_cache, _info = self.engine.run_prefill(
            jnp.asarray(prompt)[None], label=label,
            inflight=self.n_active(), tenant=req.tenant)
        if self.engine.recorder is not None:
            self.engine.recorder.annotate_prefill(
                request_id=req.request_id, tenant=req.tenant)
        self._advance_clock()
        trc = getattr(self.engine, "tracer", None)
        if trc is not None:
            # Admission spans on the request's own track, in the same
            # sim-clock coordinates as the channel events.
            track = f"req{req.request_id}"
            trc.span("queue", track, record.arrival_t, record.admit_t,
                     request=req.request_id, tenant=req.tenant,
                     queue_delay_s=record.admit_t - record.arrival_t)
            trc.span("prefill", track, record.admit_t, self.sim_time,
                     request=req.request_id, slot=slot,
                     prompt_len=len(prompt))

        seq = ActiveSeq(
            slot=slot, request=req, record=record,
            controller=self.engine.new_controller(),
            last_token=int(jnp.argmax(logits, -1)[0]),
            ledger_base=self.engine.ledger.snapshot(),
            prefill_end_t=self.sim_time)
        record.first_token_wall_ns = now_ns()
        with self.host_trace.span("sched.install"):
            self.batch_cache = self.engine.install_slot(
                self.batch_cache, kv_cache, slot)
        self.slots[slot] = seq

    def _admit(self) -> int:
        admitted = 0
        free = self._free_slots()
        while free and self.queue:
            req = self.queue[0]
            arrival = getattr(req, "arrival_time", 0.0)
            if arrival > self.sim_time:
                if self.n_active() == 0 and admitted == 0:
                    # fleet idle: fast-forward to the next arrival
                    self.sim_time = arrival
                else:
                    break
            self.queue.popleft()
            with self.host_trace.span("sched.admit", request=req.request_id,
                                      prompt_len=len(req.prompt)):
                self._admit_one(req, free.pop(0))
            admitted += 1
        return admitted

    # --------------------------------------------------------------- decode
    def _decode_step(self) -> None:
        active = [s for s in self.slots if s is not None]
        if not active:
            return
        tokens = np.zeros(self.cfg.max_batch, np.int32)
        slot_mask = np.zeros(self.cfg.max_batch, bool)
        slot_tenants: List[Optional[str]] = [None] * self.cfg.max_batch
        for seq in active:
            tokens[seq.slot] = seq.last_token
            slot_mask[seq.slot] = True
            slot_tenants[seq.slot] = seq.request.tenant
        alphas = [seq.alpha for seq in active]
        alpha = float(np.mean(alphas)) if alphas else 0.0

        step_t0 = self.sim_time
        logits, self.batch_cache, charge = self.engine.decode_batch(
            jnp.asarray(tokens), self.batch_cache,
            alpha=alpha, slot_active=slot_mask,
            slot_tenants=slot_tenants)
        with self.host_trace.span("sched.sample"):
            next_tokens = np.asarray(
                jnp.argmax(logits, axis=-1).astype(jnp.int32))
        step_latency = self._advance_clock()
        trc = getattr(self.engine, "tracer", None)
        if trc is not None:
            # One span per batched decode step on the shared steps
            # track; trc.step is the engine's step index, the id every
            # channel event of this step carries.
            trc.span("decode_step", "steps", step_t0, self.sim_time,
                     step=trc.step, n_active=len(active),
                     miss_rate=charge.miss_rate)
        self.telemetry.on_step(StepRecord(
            t=self.sim_time, n_active=len(active),
            miss_rate=charge.miss_rate, latency_s=step_latency,
            energy_j=charge.ledger_delta["total_energy_j"],
            io_stall_s=max(0.0, charge.ledger_delta.get(
                "io_stall_s", 0.0)),
            overlap_saved_s=max(0.0, charge.ledger_delta.get(
                "overlap_saved_s", 0.0)),
            per_tenant=charge.per_tenant))

        for seq in active:
            tok = int(next_tokens[seq.slot])
            seq.generated.append(tok)
            seq.last_token = tok
            if len(seq.generated) == 1:
                seq.record.first_token_t = self.sim_time
                self.telemetry.on_first_token(seq.record)
            seq.record.n_generated = len(seq.generated)
            slot_miss = float(charge.per_slot_miss[seq.slot])
            seq.record.miss_sum += slot_miss
            seq.record.miss_steps += 1
            if seq.controller is not None:
                seq.alpha = seq.controller.update(slot_miss)
            done = len(seq.generated) >= seq.request.max_new_tokens or \
                (seq.request.eos_token is not None
                 and tok == seq.request.eos_token)
            if done:
                with self.host_trace.span("sched.retire"):
                    self._retire(seq)

    def _retire(self, seq: ActiveSeq) -> None:
        rec = seq.record
        rec.finish_t = self.sim_time
        rec.finish_wall_ns = now_ns()
        trc = getattr(self.engine, "tracer", None)
        if trc is not None:
            rid = seq.request.request_id
            track = f"req{rid}"
            trc.span("decode", track, seq.prefill_end_t, self.sim_time,
                     request=rid, n_tokens=len(seq.generated),
                     ttft_s=seq.record.ttft,
                     queue_delay_s=seq.record.queue_delay)
            trc.span("retire", track, self.sim_time, self.sim_time,
                     request=rid)
        # Retirement fires on the step that produced EOS, so the token
        # list never holds tokens past it — no truncation scan needed.
        toks = np.asarray(seq.generated, np.int32)
        self.completions.append(Completion(
            request_id=seq.request.request_id,
            tokens=toks,
            prefill_s=1e-9 * (rec.first_token_wall_ns - rec.admit_wall_ns),
            decode_s=1e-9 * (rec.finish_wall_ns - rec.first_token_wall_ns),
            metrics={
                "ttft_s": seq.record.ttft,
                "queue_delay_s": seq.record.queue_delay,
                "mean_miss_rate": seq.record.mean_miss_rate,
                "alpha_final": seq.alpha,
                "prompt_truncated": seq.record.truncated,
                # Exact for max_batch=1; overlaps concurrent requests
                # otherwise (fleet totals live in telemetry.summary()).
                "decode_totals": self.engine.ledger.delta_since(
                    seq.ledger_base),
                "cache_stats": self.engine.cache.stats.snapshot(),
                # ^ likewise: the current stats window, per-request only
                #   when requests run one at a time.
            }))
        self.slots[seq.slot] = None
        self.batch_cache = self.engine.clear_slot(
            self.batch_cache, seq.slot)

    # ------------------------------------------------------------------ run
    def step(self) -> bool:
        """One scheduler tick.  Returns False when fully idle."""
        ht = self.host_trace
        with ht.span("sched.step"):
            self._admit()
            if self.n_active() == 0:
                return bool(self.queue)
            with ht.span("sched.decode"):
                self._decode_step()
            return True

    def run(self) -> List[Completion]:
        """Drive until the queue drains and every sequence retires."""
        while self.step():
            pass
        self.engine._prefetch_flush()   # settle never-used pending fills
        self.engine.cache.end_epoch()   # flush the last request's window
        return self.completions

    def summary(self, **kw) -> dict:
        kw.setdefault("per_shard", self.engine.shard_breakdown())
        kw.setdefault("placement", self.engine.placement_summary())
        pf = getattr(self.engine, "prefetcher", None)
        if pf is not None:
            kw.setdefault("prefetch", pf.summary())
        return self.telemetry.summary(
            total_energy_j=self.engine.ledger.total_energy_j, **kw)
