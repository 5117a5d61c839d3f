"""LastCommit lanes served from the speculation plane's completed
launches, in % of the lanes asked. A LastCommit is asked for each time
its block is validated (at the prevote, at the precommit, in
apply_block): once a `speculation.reconcile` span of the program, each
over the mean lanes of the window's LastCommits (the driver's counter
`lanes`); the plane counts every lane it did NOT serve (the driver's
counter `missed`, the window's growth of `SpeculationPlane.misses`).
A program or a driver without them reads nothing.
params: `asked` (span kind), `lanes`, `missed` (counters)."""

from benchmark.layer_metrics.program_span_stat import window_records


def read(readings, params):
    asked = sum(1 for r in window_records(readings)
                if r[0] == params["asked"])
    lanes = readings.counters.get(params["lanes"])
    missed = readings.counters.get(params["missed"])
    if not asked or not lanes or missed is None:
        return None
    total = asked * lanes
    # no floor at 0: more lanes missed than asked is a wrong count
    return (100.0 * (total - missed) / total,
            {"commits_asked": asked, "lanes_asked": total,
             "lanes_missed": missed})
