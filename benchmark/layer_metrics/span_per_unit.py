"""Total time of one harness span in ms over a counter the traffic
driver kept (e.g. the apply loop over blocks applied).
params: span, counter."""


def read(readings, params):
    spans = readings.spans.get(params["span"], [])
    units = readings.counters.get(params["counter"])
    if not spans or not units:
        return None
    return (sum(dur for _, dur in spans) / 1e6 / units,
            {"spans": len(spans), params["counter"]: units})
