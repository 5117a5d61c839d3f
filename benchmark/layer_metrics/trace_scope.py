"""Device time of one phase of a jitted verify program per execution,
in ms: the self times of the slice's device operations, summed over the
operations that the program's `jax.named_scope` puts under `scope`.

params: `pattern` (the program, as trace_module.py), `scope`
(`ed25519.assemble`, `ed25519.gather`, ...).

A profiler trace names a device operation by its optimized-HLO
instruction and holds no op_name, so the map {instruction: phase} comes
from the program: `expanded.structured_phases()` compiles the structured
shape launched last once more, under a cache key of its own that holds
the names, and parses the compiled text (tens of seconds where that key
misses, after the window, once a run: the map is kept for the other
phases' metrics). The self times are
`trace_reduce._self_times` over the cell's own `.xplane.pb`, the same
reduction as `breakdown.device_ops`. The note gives every phase, what no
scope covers, and the share of the slice's device time that is covered.
A program without the scopes reads nothing."""

import glob
import os

from benchmark import trace_reduce
from benchmark.harness import OUT, say
from benchmark.layer_metrics import trace_module

_PHASES: dict | None = None       # {phase | "unscoped": self seconds}


def _phase_seconds():
    """Self seconds of the newest slice's device operations by phase;
    None when the program has no phase map to give."""
    import time

    from tendermint_tpu.crypto.tpu import expanded

    phases = getattr(expanded, "structured_phases", None)
    if phases is None:
        return None
    traces = glob.glob(os.path.join(OUT, "trace", "*", "plugins", "profile",
                                    "*", "*.xplane.pb"))
    if not traces:
        return None
    t0 = time.perf_counter()
    try:
        phase_of = phases()
    except ValueError:      # no structured launch on one chip's tables
        return None
    t1 = time.perf_counter()
    from jax.profiler import ProfileData

    data = ProfileData.from_file(max(traces, key=os.path.getmtime))
    out: dict[str, float] = {}
    unmapped: dict[str, float] = {}
    for plane in data.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for ln in plane.lines:
            if ln.name != trace_reduce.OPS_LINE:
                continue
            events = [(e.start_ns, e.duration_ns, e.name) for e in ln.events]
            for name, secs in trace_reduce._self_times(events).items():
                op = trace_reduce._op_name(name)
                phase = phase_of.get(op, "unscoped")
                out[phase] = out.get(phase, 0.0) + secs
                if phase == "unscoped":
                    unmapped[op] = unmapped.get(op, 0.0) + secs
    say("phase map", instructions=len(phase_of), compile_s=t1 - t0,
        reduce_s=time.perf_counter() - t1,
        unscoped_top=sorted(unmapped.items(), key=lambda kv: -kv[1])[:5])
    return out


def read(readings, params):
    global _PHASES
    got = trace_module.read(readings, params)
    if got is None:
        return None
    executions = got[1]["executions"]
    if _PHASES is None:
        _PHASES = _phase_seconds() or {}
    total = sum(_PHASES.values())
    if params["scope"] not in _PHASES or not total:
        return None
    per_exec_ms = {k: 1e3 * v / executions for k, v in _PHASES.items()}
    return per_exec_ms[params["scope"]], {
        "executions": executions,
        "phases_ms": {k: round(v, 4) for k, v in sorted(per_exec_ms.items())},
        "covered_share": 1.0 - _PHASES.get("unscoped", 0.0) / total}
