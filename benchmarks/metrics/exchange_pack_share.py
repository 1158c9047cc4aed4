"""Exchange + stages layer: of the non-empty per-partition slices the in-HBM
exchanges cut, the share that went into a batch packed of several, in percent
(compile_service.TELEMETRY exchange_slices_packed / exchange_slices_cut,
deltas over the window; both are added once a shuffle_map stage, local and
mesh transports alike). `run_mesh_shuffle_stage` packs what it keeps for a
partition, as it keeps it, into batches of up to the rows a scan hands on
(`ops.common.adaptive_batch_rows`): a reduce task runs its programs once a
batch it is handed, so a join stage fed by a stage of many batches is handed a
few large probes and not one sliver a map-side batch. Whole slices, in order,
by contiguous copies; a partition's only slice of an exchange is left as it
lies and counts as cut, not packed: over 90 in the sort-merge cells, whose
joins read stages of many batches; 0 where every exchange carries one batch a
partition. None where the window cut no slice, or for a program without the
counters (the parent of PR 37)."""


def read(run):
    cut = run["telemetry"].get("exchange_slices_cut", 0)
    if not cut:
        return None
    return 100.0 * run["telemetry"].get("exchange_slices_packed", 0) / cut
