"""Configuration and traffic files of a benchmark cell, found by name.

A configuration is ``chipbench/configs/<name>.json``: the published
config keys as they are run, plus ``reduced`` (the published value of
each key changed), ``assumed``, ``departures``, ``engine`` (the serving
settings) and, optionally, ``family``.  A traffic mix is
``chipbench/traffic/<name>.json``.

A decoder family is ``chipbench/families/<family>.py``: everything that
depends on the attention kind and the layer stack (its sizes, the
program's ``ModelConfig``, the weight layout, the reference's layer loop
and the dense part of a step's work).  A configuration without a
``family`` key is ``mha_moe``.  Nothing here imports the program under
test: the reference reads its sizes from :func:`dims` too.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# Where family files are looked up, by file name.
FAMILIES = os.path.join(HERE, "families")
DEFAULT_FAMILY = "mha_moe"
# Loaded family modules by file path: each is executed once per process,
# so the jitted functions it defines keep their compiled programs.
_loaded: dict = {}


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


def family(name: str, where: str | None = None):
    """The decoder family ``name``: the module ``<where>/<name>.py``
    (``where`` defaults to :data:`FAMILIES`), loaded by its file path,
    so a new family is a new file and nothing else."""
    path = os.path.join(where or FAMILIES, name + ".py")
    if path not in _loaded:
        if not os.path.isfile(path):
            raise KeyError(f"no decoder family {name!r}: {path} is missing")
        spec = importlib.util.spec_from_file_location(
            "chipbench_family_" + name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def dims(conf: dict) -> dict:
    """Sizes of a configuration file, under one naming for every family:
    the sizes every family shares, then the family's own (its ``dims``).

    ``moe_layers`` holds the indices of the layers with the MoE block:
    every layer after the ``first_k_dense_replace`` leading dense ones (a
    family that spaces them otherwise sets its own).  ``norm_topk`` says
    whether the top-k gates are renormalised before they weight the
    expert outputs; the family sets what its served decoder does.
    """
    name = conf.get("family", DEFAULT_FAMILY)
    d = conf["hidden_size"]
    F = conf["moe_intermediate_size"]
    if "shared_expert_intermediate_size" in conf:
        shared = conf["shared_expert_intermediate_size"]
    else:
        shared = conf.get("n_shared_experts", 0) * F
    L = conf["num_hidden_layers"]
    eng = conf["engine"]
    common = {
        "family": name,
        "d": d,
        "layers": L,
        "moe_layers": tuple(range(conf.get("first_k_dense_replace", 0), L)),
        "dense_ff": conf["intermediate_size"],
        "experts": conf.get("num_experts", conf.get("n_routed_experts")),
        "top_k": conf["num_experts_per_tok"],
        "norm_topk": bool(conf.get("norm_topk_prob", True)),
        "expert_ff": F,
        "shared_ff": shared,
        "vocab": conf["vocab_size"],
        "eps": float(conf["rms_norm_eps"]),
        "high_bits": eng["mat_bits"][0],
        "low_bits": eng["mat_bits"][1],
        "group_size": eng["group_size"],
        "theta": eng["criticality_theta"],
        "capacity_factor": eng["capacity_factor"],
    }
    return family(name).dims(conf, common)
