"""Exchange + stages layer: the mean live rows of a slice the exchange hands a
reduce task (compile_service.TELEMETRY exchange_rows_kept /
exchange_slices_kept, deltas over the window; both are added once a
shuffle_map stage, local and mesh transports alike). A reduce task runs its
programs once a slice, so this is what coalescing a shuffle reader's input
would raise: the fact table's slices are half a million rows, a join's
re-exchanged output a few thousand. None where the window counted no slice: a
program without the counters, or a query without an exchange."""


def read(run):
    slices = run["telemetry"].get("exchange_slices_kept", 0)
    if not slices:
        return None
    return run["telemetry"].get("exchange_rows_kept", 0) / slices
