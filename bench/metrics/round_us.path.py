"""round_us.path: device-busy time in the traced window over the rounds of
Algorithm 1 run in it, in microseconds.  Busy time holds all device work of
the path (rounds, KKT checks, step sizes, BIC), so this is the device cost
of one round with its share of the rest."""


def read(run):
    rounds = sum(run.counters("rounds"))
    if run.trace is None or not rounds:
        return None
    return run.trace.busy_s / rounds * 1e6
