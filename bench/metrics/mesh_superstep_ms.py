"""Engine, across chips: the largest over the cell's chips of device
busy milliseconds (union of the chip's op intervals) over the whole
traced loop, per superstep counted by the service's
``supersteps_total``. Every superstep ends in a vote of all shards, so
the slowest chip sets the pace; on one chip this reads what
``superstep_device_ms`` reads."""
from bench import trace as tr


def chip_busy_s(run):
    """Device busy seconds of each of the cell's chips over the traced
    loop, in device order; None without a trace or a device plane."""
    if run.xplane is None:
        return None
    dev, host = tr.load(run.xplane[0])
    planes = sorted(dev)[:run.cell.chips]
    if not planes:
        return None
    off = tr.clock_offset_ns(host, run.xplane[1])
    lo, hi = (t * 1e9 + off for t in run.t_loop)
    return [sum(t - s for s, t in tr.merged(dev[p], lo, hi)) * 1e-9
            for p in planes]


def read(run):
    steps = run.counter("supersteps_total", "start", "end")
    busy = chip_busy_s(run)
    if not steps or not busy:
        return None
    return max(busy) / steps * 1e3
