#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which owns the chip: it loads the cell's files, sets up
and warms the cell's one or two shapes (all of that is `setup_s`),
measures for `--seconds`, checks what the window produced against the
plain references and prints ONE result line, last on its standard
output. It prints no result line, and exits non-zero, when JAX has no
TPU or too few chips, after any host fallback, open breaker, launch off
the chip or compile inside the window. `--rehearse` runs the same
control flow at the cell's tiny sizes on whatever backend JAX has and
never prints a result line. `--controls 1` also puts each weakened
verifier in the program's place and reports, on earlier lines, that
`correct` comes out false (README.md).
"""

import os
import sys
import time

if __name__ == "__main__" and os.environ.get("PYTHONHASHSEED") != "0":
    # str hashes are drawn anew in every process, and with them the
    # layout of every dict of names: pure-Python host code then runs a
    # few percent faster or slower from one process to the next. One
    # fixed layout for every run, on both sides of a comparison.
    os.environ["PYTHONHASHSEED"] = "0"
    os.environ["_BENCH_T0"] = repr(time.monotonic())
    os.execv(sys.executable, [sys.executable] + sys.argv)

T_START = float(os.environ.pop("_BENCH_T0", time.monotonic()))

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--controls", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def verdict(numbers: dict, what: str) -> bool:
    """Print each number compared beside its limit; all must hold."""
    from benchmark.harness import say

    ok = True
    for name, pair in numbers.items():
        if name.startswith("_"):
            continue
        value, limit = pair
        holds = value <= limit
        ok &= holds
        say(f"{what}: {name} = {value} (limit {limit})"
            + ("" if holds else "  <-- NOT within its limit"))
    if "_facts" in numbers:
        say(f"{what}: facts", **numbers["_facts"])
    return ok


def layer_metrics(run, cell, readings) -> dict:
    """Each per-layer metric through its own reader, found by the
    metric's file; a reader that finds nothing returns None and the
    metric is left out."""
    from benchmark.harness import BENCH, say

    out = {}
    for m in cell.per_layer:
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module(
            f"benchmark.layer_metrics.{spec['reader']}")
        got = reader.read(readings, spec.get("params", {}))
        if got is None:
            say(f"per-layer metric {m['name']}: nothing to read")
            continue
        value, note = got if isinstance(got, tuple) else (got, None)
        out[m["name"]] = {"value": value, "unit": m["unit"]}
        if note:
            say(f"per-layer metric {m['name']}", **note)
    return out


def main(argv=None) -> int:
    args = parse(argv)
    from tendermint_tpu.libs import jaxcache

    jaxcache.configure()
    from benchmark import harness
    from benchmark.harness import BenchFailure, say

    try:
        cell = harness.Cell(args.workload)
        device = harness.device_facts(cell.chips, args.rehearse)
        say("device", **device, cell=cell.name, seed=args.seed,
            seconds=args.seconds, trace=args.trace,
            rehearse=args.rehearse,
            cache=os.environ.get("JAX_COMPILATION_CACHE_DIR"))
        run = harness.Run(cell, args.seed, args.seconds, bool(args.trace),
                          args.rehearse, T_START)
        compiles = run.compiles = harness.CompileWatch()
        run.ledger = harness.LedgerDrain()
        driver = cell.driver().Driver(run)
        try:
            line = drive(args, cell, run, driver, device, compiles)
        finally:
            driver.close()   # every process and thread it started
    except BenchFailure as e:
        print(f"bench: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    if args.rehearse:
        say("rehearsal finished; no result line", **line)
        return 0 if line["correct"] else 1
    print(json.dumps(line), flush=True)
    return 0


def drive(args, cell, run, driver, device, compiles) -> dict:
    """Set up, warm, measure, gate, check: the result line."""
    from benchmark import harness
    from benchmark.harness import BenchFailure, say
    from tendermint_tpu.libs.tracing import TRACER

    driver.setup()
    driver.warm()
    run.ledger.drain()
    faults = harness.chip_faults(run.ledger.records, device["platform"])
    if faults:
        raise BenchFailure("set-up left the chip: " + "; ".join(faults))
    warm_records = len(run.ledger.records)
    compiles_before = compiles.count
    TRACER.clear()
    run.spans.clear()      # the window's own, not the warm-up's
    run.counters.clear()
    run.samples.clear()
    # what set-up built (a pool of 320,000 CommitSigs, a chain, a node)
    # is not garbage: keep the collector from walking it in the window
    gc.collect()
    gc.freeze()
    tslice = None
    if run.trace:
        tslice = harness.TraceSlice(cell.name, args.seconds,
                                    run.params.get("trace_slice_s", 2.0),
                                    run.params.get("trace_slice_launches"))
    setup_s = time.monotonic() - T_START
    say("set-up done", setup_s=setup_s, compiles=compiles.count,
        compile_s=round(compiles.seconds, 3), warm_launches=warm_records)

    t0 = time.perf_counter_ns()
    if tslice is not None:
        tslice.start()
    result = driver.measure(args.seconds)
    run.window_ns = (t0, time.perf_counter_ns())
    trace_path = tslice.finish() if tslice is not None else None
    run.ledger.drain()

    # the gate: nothing below may be printed under a device's name
    # unless every launch stayed on the chip and nothing compiled
    window_records = run.ledger.records[warm_records:]
    faults = harness.chip_faults(window_records, device["platform"])
    misses = [r for r in window_records if r["compile_cache"] == "miss"]
    if compiles.count != compiles_before or misses:
        faults.append(
            f"{compiles.count - compiles_before} compile(s) and "
            f"{len(misses)} new kernel shape(s) inside the window")
    if run.ledger.lost:
        faults.append(f"{run.ledger.lost} launch records evicted unread")
    if not window_records and not args.rehearse:
        faults.append("no launch reached the device inside the window")
    if faults and not args.rehearse:
        raise BenchFailure("; ".join(faults))
    say("gate", launches=len(window_records), faults=faults,
        tracer_dropped=TRACER.dropped,
        stage_p50_ms=harness.stage_medians(window_records))
    device["memory_peak_bytes"] = harness.memory_peak_bytes()

    metrics = dict(result["metrics"])
    metrics["setup_s"] = setup_s
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    missing = set(units) - set(metrics)
    if missing:
        raise BenchFailure(f"the driver reported no {sorted(missing)}")
    end_to_end = {k: {"value": metrics[k], "unit": units[k]}
                  for k in units}

    correct = verdict(driver.check(), "correct")
    if args.controls:
        for control in driver.CONTROLS:
            held = verdict(driver.check(control), f"control {control}")
            say(f"control {control}: correct = {str(held).lower()}"
                + ("  <-- the control PASSED; the check is too weak"
                   if held else ""))

    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"]}
    if run.trace:
        from benchmark.readings import Readings

        readings = Readings(run, device, window_records, trace_path,
                            tslice)
        say("end-to-end (traced run, not recorded)", **end_to_end)
        line["metrics"] = layer_metrics(run, cell, readings)
        device["busy_s"] = readings.trace["busy_s"]
        device["window_s"] = tslice.window_s
        line["breakdown"] = {"device_ops": readings.trace["device_ops"],
                             "idle_gaps": readings.trace["idle_gaps"]}
    else:
        line["metrics"] = end_to_end
    line["device"] = device
    return line


if __name__ == "__main__":
    sys.exit(main())
