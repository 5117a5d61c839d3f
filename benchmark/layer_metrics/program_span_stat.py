"""A statistic of one kind of the program's own spans
(`tendermint_tpu/libs/tracing.py`) inside the window, in ms or a count.

params: `kind`, and one of
  `stat`       "p50" | "p95" of the span's duration
  `total_per`  time spent in the kind, over ...
  `count_per`  occurrences of the kind, over ...
               ... the spans of another kind, or a counter the traffic
               driver kept (looked up in that order).

A site whose unit can be a tx records through `TRACER.leaf`, which folds
a run of repeats into one ring entry (attrs `n`, `busy_ns`): occurrences
and time are then the sums of those, so the reader takes the ring itself
(`readings.program_spans` holds no attributes), bounded by the first and
last span the harness kept. A program without the kind reads nothing."""

from benchmark.harness import pctl


def window_records(readings):
    """The ring's records (kind, span_id, parent_id, tid, start_ns,
    dur_ns, attrs) that began inside the measured window."""
    from tendermint_tpu.libs.tracing import TRACER

    if not readings.program_spans:
        return []
    lo = min(s[1] for s in readings.program_spans)
    hi = max(s[1] for s in readings.program_spans)
    return [r for r in TRACER.snapshot() if lo <= r[4] <= hi]


def occurrences(rec) -> int:
    return (rec[6] or {}).get("n", 1)


def busy_ms(rec) -> float:
    return (rec[6] or {}).get("busy_ns", rec[5]) / 1e6


def read(readings, params):
    recs = window_records(readings)
    mine = [r for r in recs if r[0] == params["kind"]]
    if not mine:
        return None
    if "stat" in params:
        p = {"p50": 50, "p95": 95}[params["stat"]]
        return pctl([r[5] / 1e6 for r in mine], p), {"spans": len(mine)}
    per = params.get("total_per") or params["count_per"]
    units = sum(occurrences(r) for r in recs if r[0] == per) \
        or readings.counters.get(per)
    if not units:
        return None
    count = sum(occurrences(r) for r in mine)
    note = {"entries": len(mine), "occurrences": count, per: units}
    if "count_per" in params:
        return count / units, note
    return sum(busy_ms(r) for r in mine) / units, note
