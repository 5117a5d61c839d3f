"""Device time of one jitted program per execution, from the profiler
trace: the TPU plane's "XLA Modules" events whose name matches
`pattern` (the jitted function's name until a tracing PR gives the
kernels stable `jax.named_scope` names). Mean over the slice, in ms."""

import re


def read(readings, params):
    pat = re.compile(params["pattern"])
    hit = {k: v for k, v in readings.trace["modules"].items()
           if pat.search(k)}
    count = sum(v["count"] for v in hit.values())
    if not count:
        return None
    total = sum(v["total_s"] for v in hit.values())
    return 1e3 * total / count, {"executions": count,
                                 "modules": sorted(hit)}
