"""Exchange + stages layer: of the planes (each column's data and validity)
the one-chip exchange's `local_xchg` programs moved into partition order, the
share moved by scatters to the rows' ranks, in percent
(compile_service.TELEMETRY exchange_planes_ranked / (exchange_planes_ranked +
exchange_planes_gathered), deltas over the window: each program's tally from
its trace, added at every dispatch). `stage_exchange.group_by_partition`
finds a row's slot by counting, not by a sort, and `ColumnBatch.place_rows`
scatters flag and 32/64-bit integer columns there (an int64 as its two
halves) and gathers the rest whole (a double: no 64-bit bitcast on the chip;
strings, dictionaries, wide decimals): a scatter of a 32-bit word costs ~5 ns
a row on a v5e where a gather by computed index costs 8-27. None where the
window dispatched no `local_xchg`, or for a program without the counters."""


def read(run):
    ranked = run["telemetry"].get("exchange_planes_ranked", 0)
    gathered = run["telemetry"].get("exchange_planes_gathered", 0)
    if not ranked + gathered:
        return None
    return 100.0 * ranked / (ranked + gathered)
