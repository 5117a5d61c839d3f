"""What one kind of the program's spans costs beyond its children: its
duration less that of its direct children (all of them, or only the
kinds in `less`), median over the window's spans, in ms.

params: `kind`, `less` (optional list of child kinds). `verify.commit`
less everything beneath it is verdict handling; `consensus.height` less
its `consensus.new_height` child is what a height costs besides the
`timeout_commit` wait. A program without the kind reads nothing."""

from benchmark.harness import median
from benchmark.layer_metrics.program_span_stat import window_records


def read(readings, params):
    recs = window_records(readings)
    mine = [r for r in recs if r[0] == params["kind"]]
    if not mine:
        return None
    less = params.get("less")
    child_ns: dict[int, int] = {}
    for r in recs:
        if less is None or r[0] in less:
            child_ns[r[2]] = child_ns.get(r[2], 0) + r[5]
    own = [(r[5] - child_ns.get(r[1], 0)) / 1e6 for r in mine]
    return median(own), {"spans": len(own),
                         "whole_p50_ms": median([r[5] / 1e6 for r in mine])}
