"""x_stream_roofline.fit: the least time the fit's rounds could take on
this chip, reading the fp32 X once per round with B and P in and out
(``costs.streaming_bytes_per_round``) at the peak HBM bandwidth, over the
device-busy time of the traced window, in percent.  The busy time also
holds the power iteration and the KKT checks, which the numerator does not
count, so the share stays under 100% whatever implements the round."""
from bench import costs


def read(run):
    rounds = sum(run.counters("rounds"))
    if run.trace is None or not rounds or run.trace.busy_s <= 0:
        return None
    c = run.cell.config
    nbytes = rounds * costs.streaming_bytes_per_round(c["m"], c["n"],
                                                      c["p"] + 1)
    return 100.0 * nbytes / run.peaks()["hbm_bytes_per_s"] / run.trace.busy_s
