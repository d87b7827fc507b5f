"""kernel_share.fit: the share of the traced window's device-busy time
spent in the program's Pallas kernels, in percent: the leaf operations
whose label (``bench/trace.py`` ``op_label``) holds ``decsvm_xpass``, the
one-pass kernel's name, or ``custom-call``.  It says how much of the fit
runs in the kernel rather than in XLA's fusions: 0 where the program has
no kernel on the fit's path."""

MARKS = ("decsvm_xpass", "custom-call")


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    kernel_s = sum(s for name, s in run.trace.op_seconds.items()
                   if any(mark in name for mark in MARKS))
    return 100.0 * kernel_s / run.trace.busy_s
