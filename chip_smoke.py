"""Bring-up check: serve Qwen1.5-MoE-A2.7B widths on one TPU chip.

Run from the repository root on a machine with one TPU:

    python chip_smoke.py

It drives the normal serving path once — ``ContinuousBatchingScheduler``
-> ``PersistentEngine`` -> jitted prefill/decode with Cache-Prior routing,
DBSC slices and quantized execution (the batched AMAT Pallas kernel) —
at the published widths of ``Qwen/Qwen1.5-MoE-A2.7B`` (config.json on
Hugging Face) with seeded random bf16 weights, and checks what comes
out:

* every request returns ``max_new`` tokens and every logit is finite;
* one request's served prefill logits agree with the dense-dequant path
  (``quant_execution=False``) on the same quantized params;
* the compiled decode step contains the Pallas kernel
  (``tpu_custom_call``).

Earlier output lines are bring-up readings (wall times of one run, not
benchmark numbers; cost-model figures are labelled "modelled").  The
last line is ``{"ok": true, "device": {...}}``.  Any failed check raises,
so the exit code is non-zero and no such line is printed.  Without a
TPU the script exits non-zero before doing anything: there is no CPU
fallback.  There is no four-chip phase: no served path spans chips yet.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when that is set,
otherwise ``.jax_cache/`` next to this file.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time
from functools import partial

_ROOT = os.path.dirname(os.path.abspath(__file__))
if os.path.join(_ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(_ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import monitoring  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.core.amat import MatConfig  # noqa: E402
from repro.core.engine import EngineConfig, PersistentEngine  # noqa: E402
from repro.core.slices import ExpertSliceStore  # noqa: E402
from repro.models import model as MDL  # noqa: E402
from repro.models.model import init_params  # noqa: E402
from repro.models.moe import RoutingPolicy  # noqa: E402
from repro.serving.scheduler import (ContinuousBatchingScheduler,  # noqa: E402
                                     Request, SchedulerConfig)

PUBLISHED_LAYERS = 24
# init_params draws each stacked leaf in f32 and scales it (two live f32
# copies): the wi leaf alone is 2 x 1.38 GB per layer, so 4 layers peak
# near 11 GB of the chip's 16 GB and 5 would pass 13.8 GB.
N_LAYERS = 4
# The DRAM-tier budget of the simulated slice cache, as a share of the
# high-bit expert store (the quickstart's ~30%).  A fixed byte count
# would be smaller than one MSB slice (~4.3 MB) at these widths.
CACHE_SHARE = 0.3
# Served (Pallas kernel) vs dense-dequant prefill logits, relative L2.
# Both paths round the *same* dequantized weights to bf16 and accumulate
# in f32; they differ in accumulation order only, which flips some bf16
# roundings of activations (2^-8 relative) layer after layer and can,
# rarely, swap a near-tied expert.  A wrong lowering (metadata rows,
# MSB shift, layout) is off by O(1).
LOGIT_REL_TOL = 5e-2
# JAX's event for one XLA backend compile (its seconds are summed).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

DEPARTURES = [
    "shared expert output is ungated (published: sigmoid-gated)",
    "top-4 gates are renormalised (published: norm_topk_prob false)",
    "capacity-based expert dispatch, capacity factor 2.0",
]


def qwen15_moe_a27b(n_layers: int):
    """Qwen1.5-MoE-A2.7B at published widths, cut to ``n_layers``."""
    base = get_config("qwen15-moe-repro")
    return dataclasses.replace(
        base, name=f"qwen15-moe-a2.7b-{n_layers}l", n_layers=n_layers,
        d_model=2048, n_heads=16, n_kv_heads=16, head_dim=128,
        qkv_bias=True, d_ff=5632, vocab_size=151936, rope_theta=1e6,
        norm_eps=1e-6, tie_embeddings=False,
        moe=dataclasses.replace(base.moe, n_experts=60, top_k=4, d_ff=1408,
                                d_ff_shared=5632),
        source="Qwen/Qwen1.5-MoE-A2.7B config.json (HF), depth cut")


class CheckedEngine(PersistentEngine):
    """A PersistentEngine that counts non-finite logits it returns."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.n_nonfinite = 0
        self.n_checked = 0

    def _check(self, logits, rows=None):
        lg = np.asarray(logits)
        if rows is not None:
            lg = lg[np.asarray(rows, bool)]
        self.n_nonfinite += int(np.count_nonzero(~np.isfinite(lg)))
        self.n_checked += lg.size

    def run_prefill(self, tokens, **kw):
        out = super().run_prefill(tokens, **kw)
        self._check(out[0])
        return out

    def decode_batch(self, token, kv_cache, **kw):
        out = super().decode_batch(token, kv_cache, **kw)
        self._check(out[0], kw.get("slot_active"))
        return out


def serve(cfg, *, n_requests: int, prompt_len: int, max_new: int,
          max_batch: int, seed: int = 0) -> dict:
    """Serve ``n_requests`` random prompts through the scheduler and check
    the results; raise on any failed check.  Returns the readings."""
    mat = MatConfig(8, 4)
    store = ExpertSliceStore.for_config(cfg, mat)
    max_seq = prompt_len + max_new + 8
    ecfg = EngineConfig(
        mat=mat, cache_bytes=CACHE_SHARE * store.total_bytes(),
        policy=RoutingPolicy(kind="cache_prior", slice_mode="dbsc",
                             quant_execution=True),
        miss_rate_target=0.05, warmup="pcw", max_seq=max_seq)

    compile_s = [0.0]

    def on_event(name, secs, **_):
        if name == COMPILE_EVENT:
            compile_s[0] += secs

    monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    engine = CheckedEngine(cfg, init_params(cfg, jax.random.PRNGKey(seed)),
                           ecfg)
    jax.block_until_ready(engine.qparams)
    setup_s = time.perf_counter() - t0

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, size=(n_requests, prompt_len),
                           dtype=np.int32)
    sched = ContinuousBatchingScheduler(
        engine, SchedulerConfig(max_batch=max_batch))
    for rid, p in enumerate(prompts):
        if not sched.submit(Request(request_id=rid, prompt=p,
                                    max_new_tokens=max_new)):
            raise RuntimeError(f"request {rid} rejected")
    # Each scheduler step ends in a host read of the sampled tokens, which
    # waits for the device, so a step's wall time covers its device work.
    step_s = []
    while True:
        t = time.perf_counter()
        busy = sched.step()
        step_s.append(time.perf_counter() - t)
        if not busy:
            break
    completions = sorted(sched.run(), key=lambda c: c.request_id)

    got = [(c.request_id, len(c.tokens)) for c in completions]
    want = [(rid, max_new) for rid in range(n_requests)]
    if got != want:
        raise RuntimeError(f"completions {got} != expected {want}")
    if engine.n_nonfinite:
        raise RuntimeError(f"{engine.n_nonfinite} of {engine.n_checked} "
                           "served logits are not finite")

    # Quantized execution vs the dense-dequant path, same params, one
    # prompt.  The served logits come from the engine's own entry point.
    tokens = jnp.asarray(prompts[:1])
    served, _, _ = engine.run_prefill(tokens)
    prefill_s = []
    for _ in range(3):   # the jitted prefill alone, warm
        t = time.perf_counter()
        jax.block_until_ready(engine._jit_prefill(engine.qparams,
                                                  tokens=tokens))
        prefill_s.append(time.perf_counter() - t)
    prefill_warm_s = float(np.median(prefill_s))
    dense_fn = jax.jit(partial(MDL.prefill, cfg=cfg, max_seq=max_seq,
                               mat=mat, quant_execution=False))
    dense, _, _ = dense_fn(engine.qparams, tokens=tokens)
    s, d = np.asarray(served, np.float64), np.asarray(dense, np.float64)
    rel_err = float(np.linalg.norm(s - d) / np.linalg.norm(d))
    if not np.isfinite(rel_err) or rel_err > LOGIT_REL_TOL:
        raise RuntimeError(f"served vs dense-dequant prefill logits: "
                           f"relative L2 {rel_err:.3e} > {LOGIT_REL_TOL}")

    # The decode program as the engine compiles it, timed alone: the
    # scheduler's step also runs the host-side cache simulation.
    decode_args = dict(
        token=jnp.zeros((max_batch,), jnp.int32), cache=sched.batch_cache,
        policy_state=engine._policy_state(), alpha=jnp.float32(0.0),
        token_mask=jnp.ones((max_batch,), bool))
    decode = engine._jit_decode.lower(engine.qparams, **decode_args).compile()
    decode_s = []
    for _ in range(5):
        t = time.perf_counter()
        jax.block_until_ready(decode(engine.qparams, **decode_args))
        decode_s.append(time.perf_counter() - t)
    monitoring.unregister_event_duration_listener(on_event)

    summary = sched.summary()
    return {
        "setup_s": setup_s,
        "compile_s": compile_s[0],
        "first_step_s": step_s[0],
        "scheduler_step_s": float(np.median(step_s[1:max_new])),
        "decode_step_s": float(np.median(decode_s)),
        "prefill_first_s": completions[0].prefill_s,
        "prefill_warm_s": prefill_warm_s,
        "n_completed": len(completions),
        "n_logits_checked": engine.n_checked,
        "logit_rel_err": rel_err,
        "decode_hlo": decode.as_text(),
        "modelled": {k: summary[k] for k in (
            "ttft_p50_s", "per_token_p50_s", "mean_miss_rate",
            "total_energy_j") if k in summary},
        "store_bytes": store.total_bytes(),
        "cache_bytes": ecfg.cache_bytes,
    }


def _emit(tag: str, **fields) -> None:
    print(json.dumps({tag: fields}), flush=True)


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_ROOT, ".jax_cache"))

    cfg = qwen15_moe_a27b(N_LAYERS)
    _emit("device", platform=dev.platform, kind=dev.device_kind,
          count=len(jax.devices()))
    _emit("config", model=cfg.name, d_model=cfg.d_model,
          n_heads=cfg.n_heads, head_dim=cfg.head_dim,
          experts=cfg.moe.n_experts, top_k=cfg.moe.top_k,
          expert_width=cfg.moe.d_ff, shared_width=cfg.moe.d_ff_shared,
          vocab=cfg.vocab_size, layers=cfg.n_layers,
          cut=f"depth {PUBLISHED_LAYERS} -> {cfg.n_layers} layers",
          weights="random bf16, seed 0", departures=DEPARTURES)

    r = serve(cfg, n_requests=4, prompt_len=128, max_new=16, max_batch=4)
    if "tpu_custom_call" not in r.pop("decode_hlo"):
        raise RuntimeError("compiled decode step has no Pallas kernel")

    mem = dev.memory_stats() or {}
    _emit("bring_up_readings",
          note="wall clock of one run; not benchmark numbers",
          peak_bytes_in_use=mem.get("peak_bytes_in_use"),
          bytes_limit=mem.get("bytes_limit"),
          setup_s=r["setup_s"],
          backend_compile_s=r["compile_s"],
          first_step_s_incl_compile=r["first_step_s"],
          prefill_first_s_incl_compile=r["prefill_first_s"],
          prefill_warm_s=r["prefill_warm_s"],
          decode_step_s=r["decode_step_s"],
          scheduler_step_s_incl_host_sim=r["scheduler_step_s"])
    _emit("checks", requests_completed=r["n_completed"],
          logits_checked_finite=r["n_logits_checked"],
          quant_vs_dense_rel_l2=r["logit_rel_err"],
          tolerance=LOGIT_REL_TOL, decode_has_pallas_kernel=True)
    _emit("modelled", note="cost model (mobile_soc profile), not measured",
          **r["modelled"], store_bytes=r["store_bytes"],
          cache_bytes=r["cache_bytes"])
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
