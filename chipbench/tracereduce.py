"""Reduce a profiler trace to the events the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
On a TPU its device plane (``/device:TPU:0``) has an ``XLA Modules`` line
(one event per program execution, named ``jit_<function>(<id>)``) and an
``XLA Ops`` line (one event per operation, named by its HLO text,
``%<instruction> = <shape> <op>(<operands>)``; the expert kernel's
instructions are ``amat_expert_matmul.<n>``).  An operation is kept under
``<function>/<instruction>``, the program it ran in and its own name, so
that an operand list never decides what it is.  The harness's own spans are
``jax.profiler.TraceAnnotation`` events named ``cb.<span>`` on a host
plane, with the span's index as the stat ``n``.  All three are read onto
one clock, in nanoseconds.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import os
from typing import Dict, List, Optional

DEVICE_PLANE = "/device:TPU:0"
KERNEL = "amat_expert_matmul"


@dataclasses.dataclass
class Trace:
    ops: List[tuple]          # (name, start_ns, end_ns) device operations
    modules: List[tuple]      # (name, start_ns, end_ns) program executions
    spans: List[tuple]        # (name, start_ns, end_ns, stats) host spans
    t0: float = 0.0           # traced window on the same clock
    t1: float = 0.0


def _stats(ev) -> Dict[str, object]:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:          # some stat values do not convert
        return {}


def op_name(hlo: str) -> str:
    """An operation event's own instruction name, from its HLO text."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def program_name(module: str) -> str:
    """``jit_decode_step(12)`` -> ``decode_step``."""
    name = module.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


# The engine jits partials, which the trace names ``_unknown``, so a step
# program is known by the harness span that launched it and waited for
# it: the program execution that overlaps that span the most.  (The
# device's clock can run a fraction of a millisecond ahead of the host's,
# so an execution may appear to start just before its span.)
STEP_SPANS = {"decode_batch": "decode", "run_prefill": "prefill"}


def label_modules(modules, spans) -> List[tuple]:
    """``(program, start, end, n)`` for each execution: ``decode`` or
    ``prefill`` with the launching span's index ``n`` for step programs,
    else the program's own name and ``None``.  ``modules`` run one after
    another on the device, so their starts and ends are both sorted."""
    out = [[program_name(m[0]), m[1], m[2], None] for m in modules]
    starts = [m[1] for m in modules]
    ends = [m[2] for m in modules]
    for name, s, e, st in spans:
        if name not in STEP_SPANS:
            continue
        lo, hi = bisect.bisect_right(ends, s), bisect.bisect_left(starts, e)
        if lo < hi:
            i = max(range(lo, hi), key=lambda i: min(e, modules[i][2])
                    - max(s, modules[i][1]))
            out[i][0], out[i][3] = STEP_SPANS[name], st.get("n")
    return [tuple(m) for m in out]


def name_ops(ops, modules) -> List[tuple]:
    """Prefix each operation with the program whose execution holds it."""
    out, mi = [], 0
    for name, s, e in ops:
        while mi < len(modules) and modules[mi][2] <= s:
            mi += 1
        prog = modules[mi][0] \
            if mi < len(modules) and modules[mi][1] <= s else "?"
        out.append((prog + "/" + op_name(name), s, e))
    return out


def from_profile(pd) -> Trace:
    ops, modules, spans = [], [], []
    for plane in pd.planes:
        if plane.name == DEVICE_PLANE:
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dst is None:
                    continue
                for ev in line.events:
                    dst.append((ev.name, ev.start_ns, ev.end_ns))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("cb."):
                        spans.append((ev.name[3:], ev.start_ns, ev.end_ns,
                                      _stats(ev)))
    for lst in (ops, modules, spans):
        lst.sort(key=lambda e: e[1])
    modules = label_modules(modules, spans)
    ops = name_ops(ops, modules)
    t0 = min([e[1] for e in spans + ops] or [0.0])
    t1 = max([e[2] for e in spans + ops] or [0.0])
    return Trace(ops, modules, spans, t0, t1)


def load(path: str) -> Trace:
    """A trace from an ``.xplane.pb`` file (optionally gzipped), or from
    the newest one under a profiler log directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return from_profile(ProfileData.from_serialized_xspace(f.read()))
    return from_profile(ProfileData.from_file(path))


# ----------------------------------------------------------- interval math
def union(intervals) -> List[tuple]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: List[list] = []
    for s, e in sorted((i[0], i[1]) for i in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(i) for i in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> List[tuple]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy, within) -> List[tuple]:
    """Parts of ``within`` (merged) not covered by ``busy`` (merged)."""
    out = []
    for s, e in within:
        cur = s
        for bs, be in busy:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


# ------------------------------------------------------------ attribution
def executions(tr: Trace, program: str) -> List[dict]:
    """Each execution of a step program (``decode`` or ``prefill``) with
    the index ``n`` of the harness span that launched it and the summed
    device time of the expert kernel's operations inside it."""
    kern = [o for o in tr.ops
            if o[0].split("/", 1)[1].startswith(KERNEL)]
    starts = [o[1] for o in kern]
    out = []
    for prog, s, e, n in tr.modules:
        if prog != program:
            continue
        kt = 0.0
        for k in range(bisect.bisect_left(starts, s), len(kern)):
            if kern[k][1] >= e:
                break
            kt += min(kern[k][2], e) - kern[k][1]
        out.append({"n": n, "start": s, "end": e, "kernel_ns": kt})
    return out


def self_times(ops) -> dict:
    """Device time of each operation name net of the operations nested in
    it (a ``while`` holds its body's operations), summed per name."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [end, name]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and e <= stack[-1][0]:
            out[stack[-1][1]] -= e - s
        out[name] = out.get(name, 0.0) + (e - s)
        stack.append([e, name])
    return out


def busy(tr: Trace) -> List[tuple]:
    """Merged intervals in which some operation ran on the device."""
    return union((o[1], o[2]) for o in tr.ops)


def label_of(tr: Trace, t: float) -> str:
    """What the host was doing at ``t``, by the innermost harness span."""
    inner: Optional[tuple] = None
    for name, s, e, _ in tr.spans:
        if s <= t < e and (inner is None or s >= inner[1]):
            inner = (name, s)
    return {"charge_decode_step": "charge path",
            "decode_batch": "decode_batch",
            "run_prefill": "admission/prefill",
            "sched_step": "scheduler other",
            "wait": "generator wait"}.get(inner[0] if inner else "",
                                          "outside spans")


def prefill_ms_per_ktok(run) -> Optional[float]:
    """Device ms of the prefill program per 1024 prompt tokens, over the
    traced prefills (``run.prefills[n][2]`` is the n-th prompt's length)."""
    if run.trace is None:
        return None
    ex = [e for e in executions(run.trace, "prefill") if e["n"] is not None]
    tokens = sum(run.prefills[e["n"]][2] for e in ex)
    if not tokens:
        return None
    return sum(e["end"] - e["start"] for e in ex) / 1e6 / (tokens / 1024)
