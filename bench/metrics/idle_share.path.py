"""idle_share.path: the share of the traced window in which no operation
ran on the device, in percent."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share()
