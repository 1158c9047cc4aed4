"""The last stdout line, with exactly the keys the driver's contract names."""

from __future__ import annotations

import json


def contract_line(correct: bool, attempted: int, failed: int, metrics: dict,
                  device: dict, breakdown: dict = None) -> str:
    keys = ("platform", "kind", "count", "memory_peak_bytes",
            "busy_s", "window_s")
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics,
            "device": {k: device[k] for k in keys if k in device}}
    if breakdown:
        line["breakdown"] = {
            "device_ops": breakdown["device_ops"][:10],
            "idle_gaps": breakdown["idle_gaps"][:10]}
    return json.dumps(line)
