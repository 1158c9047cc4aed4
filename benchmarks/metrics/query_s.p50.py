"""Median wall seconds of one query over all queries of the window: fresh
plan handed to run_plan -> result frame on the host. Caller's clock."""
import statistics


def read(run):
    return statistics.median(q["seconds"] for q in run["window"])
