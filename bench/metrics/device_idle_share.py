"""Share of the traced span in which no operation ran on the device, in
percent, the mean over the cell's chips."""


def read(run):
    if run.trace is None or not run.device_planes:
        return None
    span = run.span_s()
    if span <= 0:
        return None
    return 100.0 * (1.0 - run.busy_s() / span)
