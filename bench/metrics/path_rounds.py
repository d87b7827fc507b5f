"""path_rounds: rounds of Algorithm 1 per tuned path, summed over its grid
points (``PathResult.iters``), mean over the traced paths."""


def read(run):
    rounds = run.counters("rounds")
    return sum(rounds) / len(rounds) if rounds else None
