"""What a traced run hands to the per-layer readers: the window's
launch-ledger records, the harness's spans, the program's own spans,
the driver's counters and samples, and the reduced profiler trace."""

from __future__ import annotations

import time

from benchmark import trace_reduce
from benchmark.harness import say


class Readings:
    def __init__(self, run, device, window_records, trace_path, tslice):
        from tendermint_tpu.libs.tracing import TRACER

        self.device_kind = device["kind"]
        self.ledger = window_records
        self.spans = run.spans
        self.counters = run.counters
        self.samples = run.samples
        self.tracer_dropped = TRACER.dropped
        # the program's spans (libs/tracing.py ring) inside the window
        lo, hi = run.window_ns
        self.program_spans = [
            (r[0], r[4], r[5]) for r in TRACER.snapshot()
            if lo <= r[4] <= hi]
        if self.tracer_dropped:
            say("the program's span ring overflowed inside the window: "
                "idle gaps are attributed from what is left",
                dropped=self.tracer_dropped)
        self.trace_window_s = tslice.window_s
        t0 = time.perf_counter()
        self.trace = trace_reduce.reduce_trace(
            trace_path, sync_ns=tslice.sync_ns,
            program_spans=self.program_spans)
        say("trace", window_s=tslice.window_s, stop_s=tslice.stop_s,
            reduce_s=time.perf_counter() - t0,
            busy_s=self.trace["busy_s"], lines=self.trace["lines"],
            modules={k: v for k, v in self.trace["modules"].items()})

    def ledger_for(self, workload: str | None = None,
                   kernel: str | None = None) -> list[dict]:
        return [r for r in self.ledger
                if (workload is None or r["workload"] == workload)
                and (kernel is None or r["kernel"] == kernel)]
