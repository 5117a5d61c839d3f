"""Launch-ledger stage times (`crypto/tpu/ledger.py` `stages_ms`,
host clock): the median, over the window's launches of one workload
tag, of the sum of the named stages. params: workload, kernel
(optional), stages."""

from benchmark.harness import median


def read(readings, params):
    recs = readings.ledger_for(params.get("workload"), params.get("kernel"))
    sums = [sum(r["stages_ms"].get(s, 0.0) for s in params["stages"])
            for r in recs]
    if not sums:
        return None
    return median(sums), {"launches": len(sums)}
