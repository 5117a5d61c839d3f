"""Shared helpers of the chip-run scripts (bench.py, chip_smoke.py,
tools/profile_tpu.py): the pipelined-launch device-time estimator and
the "did every launch stay on the chip?" check.

The estimator's problem: a synced single launch measures the host
round trip (dispatch, transfer, readback) plus device execution, and
the host part dominates at small batches. Dispatching k async launches
back-to-back pipelines them on device behind ONE sync, so the
difference between two burst sizes isolates pure device execution:

    per_launch = (T(k_big) - T(k_small)) / (k_big - k_small)

Both bursts amortize exactly one round trip, so that term cancels in
the subtraction (a single-sample "burst minus single" estimate can go
negative under host jitter; the two-burst slope is robust to it).
"""

import time


def pipelined_exec_s(dispatch, k_small=4, k_big=12):
    """Estimate per-launch device execution time for `dispatch`.

    dispatch: zero-arg callable that async-dispatches one launch on
    device-resident inputs and returns a JAX array (block_until_ready
    must be valid on it).

    Returns (per_launch_s | None, single_synced_s, {k: burst_total_s}).
    per_launch_s is None when the slope came out non-positive (host
    jitter exceeded the device work — report it as unmeasurable, not
    as a garbage number).
    """
    dispatch().block_until_ready()  # warm compile/arg-kind + drain queue

    t0 = time.perf_counter()
    dispatch().block_until_ready()
    single = time.perf_counter() - t0

    def burst(k):
        t0 = time.perf_counter()
        outs = [dispatch() for _ in range(k)]
        outs[-1].block_until_ready()
        return time.perf_counter() - t0

    totals = {k_small: burst(k_small), k_big: burst(k_big)}
    per = (totals[k_big] - totals[k_small]) / (k_big - k_small)
    return (per if per > 0 else None), single, totals


def chip_faults(records=None, host_fallbacks=None, breakers=None,
                platform="tpu") -> list[str]:
    """Why this run may NOT be read as a run on `platform`: launch
    ledger records that landed on another device, raised or failed
    their sentinel; a non-zero tpu_host_fallbacks_total; a breaker
    that is not closed. Empty list = every launch stayed on the chip.
    With no arguments it reads this process's ledger, metrics and
    breakers; a parent checking a node child passes what the child's
    /debug/launches, /metrics and /status returned."""
    if records is None:
        from tendermint_tpu.crypto.tpu import ledger

        records = ledger.snapshot()
    if host_fallbacks is None:
        from tendermint_tpu.libs.metrics import tpu_metrics

        host_fallbacks = tpu_metrics().host_fallbacks.value()
    if breakers is None:
        from tendermint_tpu.crypto import batch

        breakers = batch.breaker_states()
    faults = []
    for r in records:
        where = f"{r['workload']}/{r['kernel']} launch"
        if platform not in str(r["device"]).lower():
            faults.append(f"{where} landed on {r['device']!r}")
        if r["verdict"] in ("raised", "sentinel_failed"):
            faults.append(f"{where} {r['verdict']}: {r.get('error')}")
    if host_fallbacks:
        faults.append(f"tpu_host_fallbacks_total = {host_fallbacks:g}")
    faults += [f"{name} breaker is {state}"
               for name, state in breakers.items() if state != "closed"]
    return faults
