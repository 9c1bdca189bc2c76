"""Reduce a profiler trace (``.xplane.pb``) to what the readers need.

* device busy: the union of the intervals in which an operation ran on a
  device, averaged over the devices used, and the idle gaps between them;
* device time per jitted program (the module name, ``jit_<fn>``), per
  operation (self time, named ``<program>/<op>``), and of the Pallas
  kernels in each program (the trace names a Pallas kernel only as a
  ``tpu_custom_call``, so a kernel is known by the program it runs in);
* the host's annotations (``jax.profiler.TraceAnnotation``: the harness's
  ``bench.*`` spans and the program's ``decode:WxK`` / ``prefill:WxC`` /
  ``cow:N`` dispatch labels), used to say what the host was doing in each
  idle gap: admitting requests, scheduler host work inside a step,
  dispatching, waiting for arrivals, or compiling.

Reads the trace with ``jax.profiler.ProfileData`` alone.
"""
from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
COMPILE_MARK = re.compile(r"[Cc]ompile|[Ll]ower|jaxpr_trace")
DISPATCH_MARK = re.compile(r"^(decode|prefill|cow):")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def module_name(name: str) -> str:
    """``jit_decode(5657...)`` -> ``jit_decode``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def op_name(text: str) -> str:
    """An op event's name is its HLO text; keep ``name`` (and mark a Pallas
    kernel, which the trace shows only as a ``tpu_custom_call``)."""
    short = text.split(" = ", 1)[0].lstrip("%")
    if KERNEL_TARGET in text:
        short += "[tpu_custom_call]"
    return short


def _self_times(events):
    """Self time of each op event: its duration less its children's (a
    ``while`` op encloses the ops of its body on the same line)."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    selft = [e[1] - e[0] for e in evs]
    stack: List[int] = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            selft[stack[-1]] -= e - s
        stack.append(i)
    return [(evs[i][0], evs[i][1], evs[i][2], selft[i])
            for i in range(len(evs))]


def load_events(path: str) -> dict:
    """Per device plane: module events (start_ns, end_ns, module) and op
    events (start_ns, end_ns, text); host annotation events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    mods: Dict[str, list] = defaultdict(list)
    ops: Dict[str, list] = defaultdict(list)
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        mods[plane.name].append(
                            (s, s + int(ev.duration_ns), module_name(ev.name)))
                elif line.name == OPS_LINE:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        ops[plane.name].append(
                            (s, s + int(ev.duration_ns), ev.name))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if (name.startswith("bench.") or DISPATCH_MARK.match(name)
                            or COMPILE_MARK.search(name)):
                        s = int(ev.start_ns)
                        host.append((s, s + int(ev.duration_ns), name))
    return {"modules": dict(mods), "ops": dict(ops), "host": host}


def _cause(host: list, t: int) -> str:
    """What the host was doing at ``t``: the innermost span holding it."""
    best = None
    for s, e, name in host:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    if best is None:
        return "outside the harness"
    name = best[2]
    if COMPILE_MARK.search(name):
        return "compilation"
    if DISPATCH_MARK.match(name):
        return "dispatch"
    return {"bench.submit": "admission", "bench.step": "scheduler host work",
            "bench.wait": "waiting for arrivals"}.get(name, name)


def _in_module(mods, t: int):
    """The module event holding time ``t`` (mods sorted by start)."""
    i = bisect.bisect_right(mods, (t, float("inf"), "")) - 1
    if i >= 0 and mods[i][0] <= t < mods[i][1]:
        return mods[i][2]
    return "(no program)"


def reduce(path: str, window_s: float, *, top: int = 10) -> dict:
    """Busy time, per-program and per-op device time, kernel time per
    program, and idle gaps by cause, averaged over the devices used."""
    ev = load_events(path)
    planes = sorted(ev["modules"])
    if not planes:
        raise ValueError(f"{path}: no TPU program ran in the trace")
    n = len(planes)
    busy = 0.0
    programs: Dict[str, float] = defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    kernels: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for p in planes:
        mods = sorted(ev["modules"][p])
        merged = _union([(s, e) for s, e, _ in mods])
        busy += sum(e - s for s, e in merged) / 1e9
        for s, e, name in mods:
            programs[name] += (e - s) / 1e9
        for s, e, text, self_ns in _self_times(ev["ops"].get(p, [])):
            mod = _in_module(mods, s)
            ops[f"{mod}/{op_name(text)}"] += self_ns / 1e9
            if KERNEL_TARGET in text:
                kernels[mod] += (e - s) / 1e9
        host = ev["host"]
        # idle stretches between programs, and before the first and after
        # the last one within the span of the host's annotations (the
        # harness annotates every pass of its loop, so that span is the
        # traced window)
        if host:
            lo = min(min(s for s, _, _ in host), merged[0][0])
            hi = max(max(e for _, e, _ in host), merged[-1][1])
        else:
            lo, hi = merged[0][0], merged[-1][1]
        edges = [(lo, lo)] + merged + [(hi, hi)]
        for (s0, e0), (s1, e1) in zip(edges, edges[1:]):
            if s1 > e0:
                gaps[_cause(host, (e0 + s1) // 2)] += (s1 - e0) / 1e9
    busy_s = busy / n
    avg = lambda d: {k: v / n for k, v in d.items()}
    ranked = lambda d: sorted(([k, v / n] for k, v in d.items()),
                              key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": max(0.0, 1.0 - busy_s / window_s),
        "programs": avg(programs),
        "ops": avg(ops),
        "kernels": avg(kernels),
        "gaps_by_cause": avg(gaps),
        "breakdown": {"device_ops": ranked(ops), "idle_gaps": ranked(gaps)},
        "dispatches": [name for _, _, name in ev["host"]
                       if DISPATCH_MARK.match(name)],
    }


def describe(path: str, limit: int = 12) -> str:
    """A readable summary of a trace's planes, lines and events, for
    looking at a trace by hand."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  LINE {line.name!r}: {len(evs)} events")
            names = defaultdict(lambda: [0, 0.0])
            for e in evs:
                names[e.name][0] += 1
                names[e.name][1] += e.duration_ns / 1e6
            for name, (c, ms) in sorted(names.items(),
                                        key=lambda kv: -kv[1][1])[:limit]:
                lines.append(f"    {name!r}: n={c} ms={ms:.3f}")
            for e in evs[:3]:
                lines.append(f"    e.g. {e.name[:200]!r} start={e.start_ns} "
                             f"dur={e.duration_ns} stats={dict(e.stats)}")
    return "\n".join(lines)
