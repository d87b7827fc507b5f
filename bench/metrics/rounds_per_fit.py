"""rounds_per_fit: rounds of Algorithm 1 per fit (the ``t`` that
``decsvm_fit_tol`` returns), mean over the traced fits."""


def read(run):
    rounds = run.counters("rounds")
    return sum(rounds) / len(rounds) if rounds else None
