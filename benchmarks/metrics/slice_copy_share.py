"""Exchange + stages layer: of the cuts of a contiguous range of rows out of
a batch (`ops.common.slice_batch`: a partition out of an exchanged batch
grouped by partition, a chip's share out of a batch dealt out, a frame, a
chunk), the share made by contiguous copies of every plane, in percent
(compile_service.TELEMETRY slice_copies / (slice_copies + slice_gathers),
deltas over the window, one add a call). The others were gathered by computed
index, `take(arange + start)`: on this chip 40 ms to capacity 2^19 and 100 ms
to 2^20 for three nullable 8-byte columns where the copy takes 0.11 and 0.15
ms. The batch's own columns decide: 100 wherever every exchanged column is
row-aligned (fixed-width, string, dictionary, struct), less only where a list
column rides along. None where the window counted neither: a program without
the counters, or a query without a cut."""


def read(run):
    copies = run["telemetry"].get("slice_copies", 0)
    gathers = run["telemetry"].get("slice_gathers", 0)
    if not copies + gathers:
        return None
    return 100.0 * copies / (copies + gathers)
