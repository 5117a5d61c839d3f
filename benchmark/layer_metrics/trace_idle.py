"""Share of the traced slice in which no operation ran on the device:
1 - (union of device-busy intervals, averaged over chips) / slice."""


def read(readings, params):
    window = readings.trace_window_s
    if not window:
        return None
    return 100.0 * (1.0 - readings.trace["busy_s"] / window)
