"""The sum of one attribute over the window's spans of one kind of the
program's own (`tendermint_tpu/libs/tracing.py`), over the spans of
another kind: what a per-batch span counted (votes shed at the buffer's
bound), a height. A program that registers the kind and summed nothing
reads 0.0; a program without the kind reads nothing.
params: `kind`, `attr`, `per`."""

from benchmark.layer_metrics.program_span_stat import (
    occurrences, window_records)


def read(readings, params):
    from tendermint_tpu.libs import tracing

    if params["kind"] not in tracing.registered_kinds():
        return None
    recs = window_records(readings)
    units = sum(occurrences(r) for r in recs if r[0] == params["per"])
    if not units:
        return None
    mine = [r for r in recs if r[0] == params["kind"]]
    total = sum((r[6] or {}).get(params["attr"], 0) for r in mine)
    return total / units, {"spans": len(mine), params["attr"]: total,
                           params["per"]: units}
