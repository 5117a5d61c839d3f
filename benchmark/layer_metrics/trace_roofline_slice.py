"""The structured kernel's share of its roofline in % over a profiler
slice that holds launches of SEVERAL lane counts (a vote scheduler's
micro-batches, a LastCommit's unserved lanes): the least time the chip
could take for the lanes of the slice's launches (`ops.py` over
`peaks.json`: the bound is linear in the lanes) over the kernel's whole
device time in the slice.

Time and lanes come from the SAME launches, as `trace_roofline_sr.py`
does it: the trace holds the kernel's executions inside the slice;
their lanes are those of the ledger's records of that kernel, as many
as the slice has executions, from where the slice begins (the driver's
counter `trace_slice_from_mono`, on the ledger's clock). Without the
counter the window's mean lanes stand in, and the note says so.
params: pattern (the program), kernel (the ledger's name), msg_bytes
(counter: sign bytes a lane; 0 if the driver gives none)."""

from benchmark import ops
from benchmark.layer_metrics import trace_module


def read(readings, params):
    got = trace_module.read(readings, params)
    recs = readings.ledger_for(None, params["kernel"])
    if got is None or not recs:
        return None
    executions = got[1]["executions"]
    kernel_s = got[0] / 1e3 * executions
    since = readings.counters.get("trace_slice_from_mono")
    inside = [] if since is None else \
        [r for r in recs if r["mono"] >= since][:executions]
    if len(inside) == executions:
        lanes = sum(r["lanes"] for r in inside)
    else:
        inside = []
        lanes = executions * sum(r["lanes"] for r in recs) / len(recs)
    roof = ops.roofline(readings.device_kind, lanes,
                        int(readings.counters.get(params["msg_bytes"], 0)))
    return 100.0 * roof["least_s"] / kernel_s, {
        **roof, "kernel_s": kernel_s, "lanes": lanes,
        "lanes_from": "slice" if inside else "window",
        "executions_in_slice": executions,
        "lanes_of_each": [r["lanes"] for r in inside]}
