"""Compile layer: the benchmark's clock round the first warm-up query
(trace + XLA compile on a checkout's first run, trace + persistent-cache
load on every other)."""


def read(run):
    return run["warm_first_query_s"]
