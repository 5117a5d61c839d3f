"""Lanes the launch ledger saw on the device for one workload tag:
mean per launch (`per: "launch"`), or as a share in % of a counter the
traffic driver kept (`per: <counter>`, e.g. signed txs attempted)."""


def read(readings, params):
    recs = readings.ledger_for(params.get("workload"), params.get("kernel"))
    lanes = sum(r["lanes"] for r in recs)
    if params["per"] == "launch":
        if not recs:
            return None
        return lanes / len(recs), {"launches": len(recs), "lanes": lanes}
    base = readings.counters.get(params["per"])
    if not base:
        return None
    # each admission launch carries one sentinel lane that is no tx
    lanes -= params.get("extra_lanes_per_launch", 0) * len(recs)
    return 100.0 * lanes / base, {"launches": len(recs), "lanes": lanes,
                                  params["per"]: base}
