"""Host time of a verify site: the harness's span around the call,
less the launch-ledger stages of the launch that call made (pack,
dispatch, exec, readback), median over the window's calls. What is
left is lane collection, sign-batch build and verdict handling.
params: span, workload, kernel (optional)."""

from benchmark.harness import median


def read(readings, params):
    spans = readings.spans.get(params["span"], [])
    recs = readings.ledger_for(params.get("workload"), params.get("kernel"))
    if not spans or not recs:
        return None
    launch = [sum(r["stages_ms"].values()) for r in recs]
    calls = [dur / 1e6 for _, dur in spans]
    if len(calls) == len(launch):  # one launch a call, in order
        return (median([c - l for c, l in zip(calls, launch)]),
                {"calls": len(calls), "paired": True})
    return (median(calls) - median(launch),
            {"calls": len(calls), "launches": len(launch), "paired": False})
