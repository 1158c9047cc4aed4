"""90th percentile of the window's query seconds; only where the window
holds at least 100 queries (ten samples beyond the percentile)."""
import statistics


def read(run):
    times = [q["seconds"] for q in run["window"]]
    if len(times) < 100:
        return None
    return statistics.quantiles(times, n=10, method="inclusive")[-1]
