"""Run one benchmark cell once on the chip this process holds.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``).  The run:

1. refuses anything but a TPU with the chips the cell asks for (exit 2,
   no result);
2. draws the cell's bf16 weights on the device from ``--seed`` and builds
   the served path (scheduler -> ``PersistentEngine`` -> jitted steps);
3. warms every prefill bucket, the decode shape and every slot with the
   mix's warm-up traffic (set-up ends here: ``setup_s``);
4. drives the window for ``--seconds`` of wall time and follows every
   request due in it to completion (or to the mix's drain limit);
5. reads ``memory_peak_bytes``, frees the program's state, and compares a
   seeded sample of finished requests with the plain reference
   (``chipbench/reference.py``);
6. prints, last on stdout, one JSON line: ``correct``, ``attempted``,
   ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
   ``--trace 1``), then ``checks`` with each compared number and its
   limit.  With ``--trace 0`` the metrics are the cell's end-to-end
   metrics; with ``--trace 1`` its per-layer metrics, read from a
   profiler trace of the window's last seconds and from the harness's
   spans (``chipbench/metrics/<metric>.py``, one reader each).

Diagnostics (generator lateness, compilations inside the window, queue
and slice-cache readings, the cost model's predicted step latency) go to
stderr, ending with the compared numbers and their limits.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``<repo>/.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# Each number compared and its limit; readings: PERF.md, "How correct is
# decided".  ``correct`` needs every number at or under its limit.
LIMITS = {
    # The widest gap (logit units) by which a served token lies below the
    # reference's best logit at its position.  bf16 serving against a
    # float32 reference swaps near-tied logits (sound runs read up to
    # 0.119 on a TPU v5e); the float8 control reads 0.456 and more there,
    # a token altered or a state left unchanged far above.
    "logit_gap_max": 0.25,
    # Selections of a live sequence that the routing trace reports out of
    # range or inactive: exactly none.
    "routing_faults": 0,
    # The widest share by which a served expert's boosted router score
    # lies below the reference's k-th best: bf16 serving swaps near ties
    # (sound runs read up to 0.108 on a TPU v5e); a wrong expert lies
    # far below (about 1.0).
    "route_gap_max": 0.4,
    # The largest reference gate of a selection the program ran MSB-only:
    # a gate of theta (0.5) or more is critical and needs MSB+LSB; bf16
    # gates round across theta by a little (sound runs read up to 0.506
    # on a TPU v5e; every selection run MSB-only reads 0.84 and more).
    "msb_gate_max": 0.68,
}
SAMPLE_TOKENS = 512      # served tokens compared per run, at least
SAMPLE_MIN_SEQS = 4
SAMPLE_MAX_SEQS = 16
# Traced part of the window: its last seconds.  Stopping the profiler
# writes the trace and holds the host for about a second per second
# traced, so it ends with the window and the stall falls in the drain.
TRACE_SECONDS = 3.0


def log(**kw) -> None:
    print(json.dumps(kw), file=sys.stderr, flush=True)


class RunView:
    """What a per-layer metric reader sees of a finished window."""

    def __init__(self, cell, t0, seconds, trace, dm, peaks):
        e = cell.engine
        self.t0 = t0               # window start, host clock
        self.cell, self.dm, self.peaks, self.trace = cell, dm, peaks, trace
        self.requests = [cell.records[r] for r in cell.window_rids]
        self.steps = list(e.steps)
        self.prefills = list(e.prefills)
        self.plan = list(cell.recorder.decode)
        self.spans = list(e.spans.log)
        self.seconds = seconds


def read_metric(name: str, view) -> float | None:
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"),
        os.path.join(HERE, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(view)


def pick_sample(cell, seed: int) -> list:
    """Finished window requests to compare: the longest (most served
    tokens, then longest prompt), then others drawn from the seed until
    there are ``SAMPLE_TOKENS`` served tokens and ``SAMPLE_MIN_SEQS``
    requests, or ``SAMPLE_MAX_SEQS`` requests."""
    import numpy as np

    done = [r for r in (cell.records[i] for i in cell.window_rids)
            if not r.rejected and len(r.times) == r.max_new + 1]
    if not done:
        return []
    done.sort(key=lambda r: (-(r.max_new + 1), -r.prompt_len, r.rid))
    out, rest = [done[0]], done[1:]
    order = np.random.default_rng(int(seed) % 2**64).permutation(len(rest))
    n = done[0].max_new + 1
    for i in order:
        if (n >= SAMPLE_TOKENS and len(out) >= SAMPLE_MIN_SEQS) or \
                len(out) >= SAMPLE_MAX_SEQS:
            break
        out.append(rest[i])
        n += rest[i].max_new + 1
    return out


def check(cell, sample, control: bool = False) -> dict:
    """Compare the sampled requests with the reference (program state
    must be freed first) and return the widest readings.  With
    ``control`` the float8 control stands in the program's place: its
    tokens are judged instead of the served ones."""
    import numpy as np

    from chipbench.reference import Reference, sequence_plan

    mix = cell.mix
    ref = Reference(cell.dm, cell.seed, mix["max_seq"],
                    mix["output_len"]["max"] + 1)
    out = {"logit_gap_max": 0.0, "routing_faults": 0, "route_gap_max": 0.0,
           "msb_gate_max": 0.0, "tokens_compared": 0,
           "requests_compared": len(sample)}
    slots = {}
    for si, st in enumerate(cell.steps_log):
        for j, rid in enumerate(st["rids"]):
            if rid is not None:
                slots.setdefault(rid, []).append((si, j))
    for r in sample:
        prompt = cell.prompts[r.rid]
        steps = []
        for si, j in slots[r.rid]:
            ids, act, crit, boost = cell.plan_log[si]
            steps.append((ids, act, crit, j, boost))
        served = np.asarray([cell.first_tokens[r.rid]]
                            + list(cell.completions[r.rid]), np.int32)
        *plan, bad = sequence_plan(cell.dm, cell.prefill_plan[r.rid], steps,
                                   mix["max_batch"])
        out["routing_faults"] += bad
        log(reference=dict(request=r.rid, prompt=len(prompt),
                           served=len(served)))
        judged = ref.control_tokens(prompt, served, plan) if control \
            else served
        got = ref.compare(prompt, served, judged, plan)
        for name in ("logit_gap", "route_gap", "msb_gate"):
            out[name + "_max"] = max(out[name + "_max"], got[name])
        out["tokens_compared"] += len(served)
    return out


def dropped_share(cell) -> dict:
    """Share of active token-expert selections that capacity dispatch
    dropped, in the window's decode steps and prefills."""
    from chipbench.reference import capacity, keep_mask

    dm = cell.dm
    E, k, f = dm["experts"], dm["top_k"], dm["capacity_factor"]
    out = {}
    for kind, batches in (
            ("decode", [(ids[li, 0], act[li, 0])
                        for ids, act, _ in cell.recorder.decode
                        for li in range(ids.shape[0])]),
            ("prefill", [(ids[li, 0], None)
                         for ids in cell.recorder.prefill.values()
                         for li in range(ids.shape[0])])):
        sel = drop = 0
        for ids, act in batches:
            keep = keep_mask(ids, E, capacity(ids.shape[0], k, E, f))
            act = ids < E if act is None else act
            sel += int(act.sum())
            drop += int((act & ~keep).sum())
        out[kind] = drop / sel if sel else None
    return out


def breakdown(tr) -> dict:
    from collections import defaultdict

    from chipbench.tracereduce import busy, gaps, label_of, self_times

    top = sorted(self_times(tr.ops).items(), key=lambda kv: -kv[1])[:10]
    idle = defaultdict(float)
    for s, e in gaps(busy(tr), [(tr.t0, tr.t1)]):
        key = label_of(tr, (s + e) / 2) if e - s >= 1e5 else \
            "short gaps under 0.1 ms"
        idle[key] += e - s
    gl = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, v / 1e9] for n, v in top],
            "idle_gaps": [[n, v / 1e9] for n, v in gl]}


def serve(workload: dict, conf: dict, mix: dict, seed: int,
          seconds: float, trace: bool, bench: dict,
          require_tpu: bool = True, t_start: float | None = None):
    """Set up a cell, drive its window and read its metrics; then free
    the program's state.  Returns ``(cell, result line without
    correct)``, or None when the device is not what the cell needs."""
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu"
                        or len(devs) < workload["chips"]):
        log(refused=f"needs {workload['chips']} TPU chip(s); found "
                    f"{len(devs)} x {devs[0].platform}")
        return None
    from chipbench import harness
    from chipbench.work import peaks

    pk = peaks(devs[0].device_kind) if devs[0].platform == "tpu" else \
        peaks("TPU v5 lite")
    t_start = T_START if t_start is None else t_start
    cell = harness.Cell(conf, mix, seed)
    cell.setup()
    setup_s = time.perf_counter() - t_start
    n_compiles0 = cell.n_compiles

    if cell.traffic.saturated:
        reqs = None
    else:
        reqs = cell.traffic.window(seconds)
    tdir = None
    t_at = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-",
                                dir=os.environ.get("TMPDIR"))
        t_at = (max(0.0, seconds - TRACE_SECONDS), seconds, tdir)
    t0 = cell.drive(reqs, seconds, drain_limit=mix["drain_limit_s"],
                    trace_at=t_at)
    window_compiles = cell.n_compiles - n_compiles0
    e2e = harness.end_to_end(cell, seconds)
    device = harness.device_summary()

    metrics = {}
    out = {}
    if trace:
        from collections import Counter

        from chipbench import tracereduce
        tr = tracereduce.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        log(trace=dict(
            programs=Counter(m[0] for m in tr.modules).most_common(8),
            ops=len(tr.ops), spans=len(tr.spans),
            seconds=(tr.t1 - tr.t0) / 1e9))
        view = RunView(cell, t0, seconds, tr, cell.dm, pk)
        names = [m for m in bench["per_layer"]
                 if workload["name"] in m.get("workloads",
                                              [workload["name"]])]
        for m in names:
            v = read_metric(m["name"], view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy_ns = tracereduce.length(tracereduce.busy(tr))
        device["busy_s"] = busy_ns / 1e9
        device["window_s"] = (tr.t1 - tr.t0) / 1e9
        out["breakdown"] = breakdown(tr)
    else:
        for m in bench["end_to_end"]:
            if workload["name"] not in m.get("workloads",
                                             [workload["name"]]):
                continue
            v = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    lat = sorted(cell.lateness)
    summ = cell.sched.summary()
    log(window=dict(
        seconds=seconds, setup_s=setup_s, attempted=e2e["attempted"],
        failed=e2e["failed"],
        compiles_in_window=window_compiles,
        generator_lateness_ms_p50=1e3 * lat[len(lat) // 2] if lat else None,
        generator_lateness_ms_max=1e3 * lat[-1] if lat else None,
        decode_steps=len(cell.engine.steps),
        prefills=len(cell.engine.prefills),
        queue_left=len(cell.sched.queue),
        capacity_dropped_share=dropped_share(cell),
        slice_miss_rate=summ.get("mean_miss_rate"),
        measured_step_ms=1e3 * sum(s["t1"] - s["t0"] for s in cell.engine.steps)
        / max(1, len(cell.engine.steps)),
        modelled_step_ms=1e3 * summ["per_token_p50_s"]
        if summ.get("per_token_p50_s") is not None else None,
        peak_hbm_bytes=device["memory_peak_bytes"],
        **{k: v for k, v in e2e.items() if k not in ("attempted", "failed")}))

    # Keep what the comparison needs and free the program.
    cell.keep_for_check()
    cell.free()
    log(freed=True)
    result = {"attempted": e2e["attempted"], "failed": e2e["failed"],
              "metrics": metrics, "device": device}
    result.update(out)
    return cell, result


def judge(cell, result: dict, control: bool = False) -> dict:
    """The result line of a served window: the comparison with the
    reference decides ``correct``; ``checks`` comes last."""
    t = time.perf_counter()
    chk = check(cell, pick_sample(cell, cell.seed), control=control)
    log(compared=dict(requests=chk["requests_compared"],
                      tokens=chk["tokens_compared"], control=control,
                      seconds=time.perf_counter() - t))
    checks = {name: {"value": chk[name], "limit": limit}
              for name, limit in LIMITS.items()}
    correct = chk["requests_compared"] > 0 and \
        all(c["value"] <= c["limit"] for c in checks.values())
    line = {"correct": correct}
    line.update(result)
    line["checks"] = checks
    return line


def run_cell(workload: dict, conf: dict, mix: dict, seed: int,
             seconds: float, trace: bool, bench: dict,
             require_tpu: bool = True, control: bool = False,
             t_start: float | None = None) -> dict | None:
    """One run of a cell; returns the result line (a dict), or None when
    the device is not what the cell needs.  With ``control`` the float8
    control is judged in the program's place."""
    served = serve(workload, conf, mix, seed, seconds, trace, bench,
                   require_tpu=require_tpu, t_start=t_start)
    if served is None:
        return None
    return judge(*served, control=control)


def use_compile_cache() -> None:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else a fixed directory in the checkout; every program is kept."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    from chipbench.cellconfig import benchmark, cell

    bench = benchmark()
    workload, conf, mix = cell(a.workload)
    use_compile_cache()
    res = run_cell(workload, conf, mix, a.seed, a.seconds, bool(a.trace),
                   bench)
    if res is None:
        return 2
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
