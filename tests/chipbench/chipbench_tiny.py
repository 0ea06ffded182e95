"""A tiny cell for the benchmark's CPU tests: the Qwen configuration file
and the chat mix, shrunk to sizes Pallas interpret mode runs in seconds."""

import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench.cellconfig import load_json  # noqa: E402

WORKLOAD = {"name": "tiny.chat", "chips": 1}
SEED = 2**31 + 11


def conf() -> dict:
    c = load_json("configs", "qwen15-moe-a2.7b.json")
    c.update(hidden_size=64, intermediate_size=128, moe_intermediate_size=64,
             shared_expert_intermediate_size=128, num_experts=8,
             num_experts_per_tok=2, num_attention_heads=2,
             num_key_value_heads=2, num_hidden_layers=2, vocab_size=512)
    return c


def mix() -> dict:
    m = load_json("traffic", "chat.json")
    m.update(arrivals={"kind": "poisson", "rate_per_s": 6.0}, max_batch=3,
             max_seq=256,
             prompt_len={"kind": "lognormal", "median": 24, "sigma": 0.8,
                         "min": 8, "max": 64, "buckets": [32, 64]},
             output_len={"kind": "lognormal", "median": 4, "sigma": 0.6,
                         "min": 2, "max": 8},
             drain_limit_s=60, warm_new_tokens=2)
    return m


def bench() -> dict:
    e2e = ("ttft_p90_ms", "itl_p95_ms", "tpot_ms", "tokens_per_s", "setup_s")
    per_layer = ("queue_wait_p90_ms", "batch_occupancy", "charge_ms_per_step",
                 "step_mfu.decode")
    return {"end_to_end": [{"name": n, "unit": "u"} for n in e2e],
            "per_layer": [{"name": n, "unit": "u"} for n in per_layer]}


def plan(dm, rng, n_layers, P=6, B=3, n_steps=2):
    """A routing plan of one request: a prefill of ``P`` and ``n_steps``
    decode steps in slot 1 of ``B``, drawn from ``rng``."""
    E, k = dm["experts"], dm["top_k"]

    def pick(rows):
        return np.stack([np.stack([rng.permutation(E)[:k]
                                   for _ in range(rows)])[None]
                         for _ in range(n_layers)]).astype(np.int32)

    prefill = pick(P)
    steps = []
    for _ in range(n_steps):
        sid = pick(B)
        act = np.ones((n_layers, 1, B, k), bool)
        crit = rng.random((n_layers, 1, B, k)) < 0.5
        boost = (1.0 + 0.3 * (rng.random((n_layers, E)) < 0.5)) \
            .astype(np.float32)
        steps.append((sid, act, crit, 1, boost))
    return prefill, steps
