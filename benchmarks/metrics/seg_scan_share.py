"""Streaming ops: the share of an agg_collapse program's per-group reductions
(ops/segment: sums, counts, any, min, max, first over a key-sorted batch) that
were built as scans, of all it was dispatched with over the window, in percent
(compile_service.TELEMETRY seg_scan_reductions and seg_scatter_reductions: each
program's tally from its trace, added at every dispatch). A scatter-add into as
many segments as slots costs ~70 ns an element on this chip, a scan plus a
gather a tenth of that. None where the window counted neither: a program
without the counters, or a query without an agg_collapse."""


def read(run):
    scan = run["telemetry"].get("seg_scan_reductions", 0)
    scatter = run["telemetry"].get("seg_scatter_reductions", 0)
    if not scan + scatter:
        return None
    return 100.0 * scan / (scan + scatter)
