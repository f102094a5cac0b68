"""Engine: device busy milliseconds (union of the device's op
intervals) per superstep, over the whole traced loop, with the
supersteps counted by the service's own ``supersteps_total``."""


def read(run):
    steps = run.counter("supersteps_total", "start", "end")
    if run.loop_device is None or not steps:
        return None
    return run.loop_device.busy_s / steps * 1e3
