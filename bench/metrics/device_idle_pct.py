"""Device: share of the traced window in which no operation ran on the
device, from the profiler trace."""


def read(run):
    if run.device is None or run.device.window_s <= 0:
        return None
    return (1.0 - run.device.busy_s / run.device.window_s) * 100.0
