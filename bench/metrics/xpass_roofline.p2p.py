"""xpass_roofline.p2p: the one-pass kernel's share of its roofline, in
percent: the bytes of X its products read on one chip at the peak HBM
bandwidth, over the device time of the operations labelled
``decsvm_xpass*`` (``bench/trace.py`` ``op_label``; a mean over the cell's
chips, as the bytes are one chip's).

A fit of r rounds makes r + ceil(r / check_every) + 51 products over X: a
gradient a round, one at the network mean a KKT check, and the power
iteration's 50 steps and its Rayleigh quotient.  Each reads the chip's
share of X once, 4 (m / chips) n (p + 1) bytes.  Nothing where the trace
holds no such kernel."""
import math

MARK = "decsvm_xpass"
POWER_PRODUCTS = 51


def read(run):
    if run.trace is None:
        return None
    kernel_s = sum(s for name, s in run.trace.op_seconds.items()
                   if name.startswith(MARK))
    rounds = run.counters("rounds")
    if kernel_s <= 0 or not rounds:
        return None
    c = run.cell.config
    products = sum(r + math.ceil(r / c["check_every"]) + POWER_PRODUCTS
                   for r in rounds)
    chip_bytes = 4 * (c["m"] // run.cell.chips) * c["n"] * (c["p"] + 1)
    return (100.0 * products * chip_bytes / run.peaks()["hbm_bytes_per_s"]
            / kernel_s)
