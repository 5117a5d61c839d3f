"""From a profiler trace (`.xplane.pb`) to the device's numbers.

    busy_s        union of the intervals in which an operation ran on a
                  device, averaged over the devices traced
    modules       device time of each jitted program (the TPU plane's
                  "XLA Modules" line: one event per execution)
    device_ops    the operations that took most device time (self
                  time: a `while` does not count its body twice)
    idle_gaps     the device's idle time by what the host was doing in
                  it: the harness's `bench:*` TraceAnnotations, and the
                  program's own spans laid on the trace's clock through
                  the `bench:clock_sync` annotation

Read with nothing but JAX (`jax.profiler.ProfileData`). Every PR
computes the same numbers the same way; `tests/test_trace_reduce.py`
holds it to a small trace recorded on the chip.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC = "bench:clock_sync"
MIN_GAP_NS = 20_000
MAX_LABELLED = 2000  # longest gaps per device that get a label


def _op_name(name: str) -> str:
    """`%fusion.7 = s32[...] fusion(...)` -> `fusion.7`: the trace names
    an operation by its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """{name: self seconds} of nested events on one line."""
    totals: dict[str, float] = {}
    stack = []  # (end, name, [child time])
    for s, d, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= s:
            end, nm, child = stack.pop()
            totals[nm] = totals.get(nm, 0.0) + child[1] - child[0]
        if stack:
            stack[-1][2][0] += d
        stack.append((s + d, name, [0.0, d]))
    for end, nm, child in stack:
        totals[nm] = totals.get(nm, 0.0) + child[1] - child[0]
    return {k: v / 1e9 for k, v in totals.items()}


def _label_gap(gap, spans):
    """Split one idle gap over the host spans that overlap it: at each
    instant the shortest covering span names what the host was doing."""
    g0, g1 = gap
    over = [(s, e, n) for s, e, n in spans if s < g1 and e > g0]
    cuts = sorted({g0, g1, *(min(max(x, g0), g1)
                             for s, e, _ in over for x in (s, e))})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [(e - s, n) for s, e, n in over if s <= mid < e]
        name = min(cover)[1] if cover else "host:unattributed"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce_trace(path: str, *, sync_ns: int | None = None,
                 program_spans=()) -> dict:
    """`program_spans`: (name, perf_counter_ns start, duration ns) of
    spans recorded outside the profiler; they need `sync_ns`, the
    perf_counter_ns at which `bench:clock_sync` was emitted."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices = {}
    host = []
    sync_at = None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: [(e.start_ns, e.duration_ns, e.name)
                               for e in ln.events] for ln in plane.lines}
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == SYNC and sync_at is None:
                        sync_at = e.start_ns
                    elif e.name.startswith("bench:"):
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name[len("bench:"):]))
    if not devices:
        raise ValueError(f"no /device:TPU plane in {path}: "
                         f"{[p.name for p in data.planes]}")
    if program_spans:
        if sync_ns is None or sync_at is None:
            raise ValueError("program spans need the clock sync")
        shift = sync_at - sync_ns
        host += [(t0 + shift, t0 + shift + dur, name)
                 for name, t0, dur in program_spans]

    busy_s, modules, op_self, gaps_by = [], {}, {}, {}
    for name, lines in sorted(devices.items()):
        ops = lines.get(OPS_LINE) or [
            ev for ln, evs in lines.items() if ln != MODULES_LINE
            for ev in evs]
        busy = _union((s, s + d) for s, d, _ in ops if d > 0)
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        for s, d, nm in lines.get(MODULES_LINE, []):
            m = modules.setdefault(re.sub(r"\(\d+\)$", "", nm),
                                   {"count": 0, "total_s": 0.0})
            m["count"] += 1
            m["total_s"] += d / 1e9
        for nm, secs in _self_times(ops).items():
            nm = _op_name(nm)
            op_self[nm] = op_self.get(nm, 0.0) + secs
        gaps = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _)
                       in zip(busy, busy[1:])), reverse=True)
        for i, (ns, e0, s1) in enumerate(gaps):
            if i >= MAX_LABELLED or ns < MIN_GAP_NS:
                rest = sum(g[0] for g in gaps[i:])
                gaps_by["gaps too short to label"] = gaps_by.get(
                    "gaps too short to label", 0.0) + rest / 1e9
                break
            for label, part in _label_gap((e0, s1), host).items():
                gaps_by[label] = gaps_by.get(label, 0.0) + part / 1e9
    n = len(devices)

    def top(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "devices": n,
        "busy_s": sum(busy_s) / n,
        "modules": modules,
        "device_ops": top(op_self),
        "idle_gaps": top(gaps_by),
        "lines": {p: {ln: len(ev) for ln, ev in lines.items()}
                  for p, lines in devices.items()},
    }
