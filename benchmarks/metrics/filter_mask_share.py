"""Whole-stage and agg: of the batches a FilterExec gave its verdict on over
the window, the share whose verdict went into the collapse of the partial
aggregate the filter feeds as a mask, in percent
(compile_service.TELEMETRY filter_masks_carried / (filter_masks_carried +
filter_compactions), each added at every dispatch). The others were compacted
by the filter's own program (`fused.filter…`): every kept row of every plane
moved to its rank, a double plane by two 32-bit gathers of 41-55 ms a 2^21
batch on this chip, before a collapse whose sort sends dead slots last anyway.
100 where every filter of the query feeds a partial aggregate directly (the
q06core cells), 0 where the filters feed joins (q3). None where the window
counted neither: a program without the counters, or a query without a
filter."""


def read(run):
    carried = run["telemetry"].get("filter_masks_carried", 0)
    compacted = run["telemetry"].get("filter_compactions", 0)
    if not carried + compacted:
        return None
    return 100.0 * carried / (carried + compacted)
