"""Streaming ops: of the per-group sums the window's agg_collapse programs
were dispatched with (every ops/segment.seg_sum over numbers: the sums of sum
and avg and the merged counts; flags counted aside), the share that added an
integer array, in percent (compile_service.TELEMETRY seg_int_sums / seg_sums:
seg_sum tallies the dtype of the array it is handed when a program is traced,
and the tally is added at every dispatch). In the decimal cell the money sum
adds the decimal's unscaled longs and the avg's sum doubles (Spark's plan), so
with the counts it reads between a half and three quarters; the day money is
cast to double before the reduction it falls to the double-typed twin's
reading. A double adds SF1's cents exactly (a group's sum stays under 2^43),
so the comparison of the answers cannot tell: this can. None where the window
counted no sum: a program without the counters, or no agg_collapse."""


def read(run):
    sums = run["telemetry"].get("seg_sums", 0)
    if not sums:
        return None
    return 100.0 * run["telemetry"].get("seg_int_sums", 0) / sums
