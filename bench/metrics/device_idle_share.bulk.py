"""Device: the share of the traced window in which no operation ran on
the chip (1 minus the union of operation intervals over the window)."""


def read(run):
    window = run.trace_t1 - run.trace_t0
    if window <= 0 or not run.device_trace["chips_seen"]:
        return None
    return (1.0 - run.device_trace["busy_s"] / window) * 100.0
