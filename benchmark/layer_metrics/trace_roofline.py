"""A kernel's share of its roofline in %: the least time the chip could
take for the launch (`ops.py` over `peaks.json`, keyed by device kind)
over the kernel's device time per execution from the trace. params:
pattern (the program), lanes (counter: lanes a launch carries),
msg_bytes (counter: sign bytes a lane). The note says which bound."""

from benchmark import ops
from benchmark.layer_metrics import trace_module


def read(readings, params):
    got = trace_module.read(readings, params)
    lanes = readings.counters.get(params["lanes"])
    if got is None or not lanes:
        return None
    kernel_s = got[0] / 1e3
    roof = ops.roofline(readings.device_kind, lanes,
                        int(readings.counters.get(params["msg_bytes"], 0)))
    return 100.0 * roof["least_s"] / kernel_s, {**roof, "kernel_s": kernel_s,
                                                "lanes": lanes}
