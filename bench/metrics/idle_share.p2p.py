"""idle_share.p2p: the share of the traced window in which no operation
ran on the device, in percent, a mean over the cell's chips."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share()
