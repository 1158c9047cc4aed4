"""What says that something other than the compiled device path served a
query. The list of counters is the benchmark's own copy of
spark/validator._FALLBACK_COUNTERS (PR 21), so a later PR cannot shorten it.
"""

from __future__ import annotations

# run_info counters that stay zero unless the query was served, in part, by
# task retries, the degradation ladder down to the CPU row interpreter,
# breaker reroutes, the pool->thread degrade or process-pool stages
FALLBACK_COUNTERS = ("retries", "degradations", "ladder_rung",
                     "task_fallbacks", "breaker_trips", "breaker_reroutes",
                     "bytes_copied_fallback", "pool_stages")


def refusals(run_info: dict, chips: int, exchange_width: int) -> list:
    """Why this query does not count as served by the device path ([] = it
    does): a fallback counter, a spill, an exchange through files, or an
    exchange on fewer devices than min(chips, width)."""
    found = [f"{k}={v}" for k, v in sorted(run_info.items())
             if (k in FALLBACK_COUNTERS or k.startswith("errors.")) and v]
    if run_info.get("spill_count"):
        found.append(f"spill_count={run_info['spill_count']}")
    if run_info.get("file_stages"):
        found.append(f"file_stages={run_info['file_stages']}")
    if run_info.get("mesh_stages") and \
            run_info.get("mesh_devices") != min(chips, exchange_width):
        found.append(f"mesh_devices={run_info.get('mesh_devices')} "
                     f"(want {min(chips, exchange_width)})")
    return found
