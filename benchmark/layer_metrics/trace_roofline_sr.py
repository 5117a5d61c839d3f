"""The sr25519 kernel's share of its roofline in %: the least time the
chip could take for a launch (`ops_sr.py` over `peaks.json`) over the
kernel's device time per execution from the trace. params: pattern (the
program), kernel (the launch ledger's name for it).

Time and lanes come from the SAME launches. The trace holds the
kernel's executions inside the profiler slice; their lanes are those of
the ledger's records of that kernel, as many as the slice has
executions, from where the slice begins (the driver's counter
`trace_slice_from_mono`, on the ledger's clock). A driver that gives
no such counter is read over the window's launches, and the note says
so: the two then agree only as far as the slice is typical."""

from benchmark import ops_sr
from benchmark.layer_metrics import trace_module


def read(readings, params):
    got = trace_module.read(readings, params)
    recs = readings.ledger_for(None, params["kernel"])
    if got is None or not recs:
        return None
    kernel_s = got[0] / 1e3
    executions = got[1]["executions"]
    since = readings.counters.get("trace_slice_from_mono")
    inside = [] if since is None else \
        [r for r in recs if r["mono"] >= since][:executions]
    lanes_of = inside or recs
    lanes = sum(r["lanes"] for r in lanes_of) / len(lanes_of)
    roof = ops_sr.roofline(readings.device_kind, lanes)
    return 100.0 * roof["least_s"] / kernel_s, {
        **roof, "kernel_s": kernel_s, "lanes_per_launch": lanes,
        "lanes_from": "slice" if inside else "window",
        "launches_counted": len(lanes_of),
        "executions_in_slice": executions}
