"""A percentile of samples the traffic driver (or its load generator)
took on its own clock. params: sample, percentile."""

from benchmark.harness import pctl


def read(readings, params):
    vals = readings.samples.get(params["sample"])
    if not vals:
        return None
    return pctl(vals, params["percentile"]), {"samples": len(vals)}
