"""Configuration and traffic files of a benchmark cell, found by name.

A configuration is ``chipbench/configs/<name>.json``: the published
config keys as they are run, plus ``reduced`` (the published value of
each key changed), ``assumed``, ``departures`` and ``engine`` (the
serving settings).  A traffic mix is ``chipbench/traffic/<name>.json``.
Nothing here imports the program under test: the reference reads its
sizes from :func:`dims` too.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(workload: str) -> tuple[dict, dict, dict]:
    """(workload entry, configuration file, traffic file) of a cell."""
    bench = benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"have {sorted(entries)}")
    w = entries[workload]
    return (w, load_json("configs", w["config"] + ".json"),
            load_json("traffic", w["traffic"] + ".json"))


def dims(conf: dict) -> dict:
    """Sizes of a configuration file, under one naming for every family."""
    d = conf["hidden_size"]
    heads = conf["num_attention_heads"]
    F = conf["moe_intermediate_size"]
    if "shared_expert_intermediate_size" in conf:
        shared = conf["shared_expert_intermediate_size"]
    else:
        shared = conf.get("n_shared_experts", 0) * F
    if conf.get("first_k_dense_replace", 0):
        raise ValueError("a leading dense layer cannot be served: the "
                         "program's layer patterns only repeat")
    eng = conf["engine"]
    return {
        "d": d,
        "heads": heads,
        "kv_heads": conf.get("num_key_value_heads", heads),
        "head_dim": conf.get("head_dim", d // heads),
        "layers": conf["num_hidden_layers"],
        "dense_ff": conf["intermediate_size"],
        "experts": conf.get("num_experts", conf.get("n_routed_experts")),
        "top_k": conf["num_experts_per_tok"],
        "expert_ff": F,
        "shared_ff": shared,
        "vocab": conf["vocab_size"],
        "rope_theta": float(conf["rope_theta"]),
        "eps": float(conf["rms_norm_eps"]),
        "qkv_bias": bool(conf.get("qkv_bias", conf.get("attention_bias",
                                                       False))),
        "high_bits": eng["mat_bits"][0],
        "low_bits": eng["mat_bits"][1],
        "group_size": eng["group_size"],
        "theta": eng["criticality_theta"],
        "capacity_factor": eng["capacity_factor"],
    }
