"""The thread-CPU time the program's spans carry (attr `cpu_ns`, which
`tendermint_tpu/libs/tracing.py` stamps on the kinds whose body is one
thread's synchronous work), inside the window, in ms.

params: `kinds` (list), `per`, and `what`:
  "cpu"   the sum of `cpu_ns`: what the thread ran
  "wait"  the sum of duration less `cpu_ns`: what the thread held the
          span and did not run (the interpreter lock's queue; under a
          span that writes or launches, the disk or the device too)
          ... over the spans of the kind `per`, or a counter the
          traffic driver kept (looked up in that order).

Only records that carry the attribute count: a span ended on another
thread than it began on carries none, and a program from before the
attribute carries it nowhere and reads nothing. The note gives the
entries read and the share of the kinds' records that carried it.
Where the host's thread clock advances in ticks (10 ms on the chip's
host) a span's `cpu_ns` is 0 or whole ticks: the sum is still a
reading once the kinds hold some hundreds of ms in the window, and
`wait` can come out below 0 for kinds that hold less."""

from benchmark.layer_metrics.program_span_stat import (
    occurrences, window_records)


def read(readings, params):
    recs = window_records(readings)
    kinds = set(params["kinds"])
    mine = [r for r in recs if r[0] in kinds]
    stamped = [r for r in mine if "cpu_ns" in (r[6] or {})]
    if not stamped:
        return None
    per = params["per"]
    units = sum(occurrences(r) for r in recs if r[0] == per) \
        or readings.counters.get(per)
    if not units:
        return None
    if params["what"] == "cpu":
        total_ns = sum(r[6]["cpu_ns"] for r in stamped)
    else:
        total_ns = sum(r[5] - r[6]["cpu_ns"] for r in stamped)
    return total_ns / 1e6 / units, {
        "entries": len(stamped), per: units,
        "stamped_share": len(stamped) / len(mine)}
