"""Share of the traced window in which no operation ran on the device:
100 * (1 - union of device op intervals / traced window)."""
LAYER = "device"
UNIT, BETTER, SOURCE = "%", "lower", "device_trace"
MOVES, TASK = "path_s", "path"


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
