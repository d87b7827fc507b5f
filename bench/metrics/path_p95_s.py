"""path_p95_s: the 95th percentile of the wall times of all paths completed
in the window (``statistics.quantiles``, inclusive method)."""
import statistics


def read(run):
    lat = run.latencies()
    if len(lat) < 20:
        return None
    return statistics.quantiles(lat, n=100, method="inclusive")[94]
