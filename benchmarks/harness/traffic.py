"""The one traffic generator: a closed loop over a weighted mix of queries.

A traffic file (benchmarks/traffic/<name>.json) holds
`{"loop": "closed", "clients": n, "mix": [{"query": q, "weight": w,
"params": {...}}, ...]}`. A parameter is a literal, or `{"choice": [...]}`
drawn per iteration. Every seed sees the same multiset of queries per pass
through the mix, in another order.
"""

from __future__ import annotations

import numpy as np


def schedule(traffic: dict, seed: int, client: int = 0):
    """Endless (query name, params) draws for one client."""
    if traffic.get("loop") != "closed":
        raise SystemExit("traffic: only a closed loop is generated")
    rng = np.random.default_rng([int(seed), client])
    cycle = [e for e in traffic["mix"] for _ in range(int(e["weight"]))]
    while True:
        for i in rng.permutation(len(cycle)):
            entry = cycle[i]
            yield entry["query"], {
                k: (v["choice"][rng.integers(len(v["choice"]))]
                    if isinstance(v, dict) else v)
                for k, v in entry.get("params", {}).items()}
