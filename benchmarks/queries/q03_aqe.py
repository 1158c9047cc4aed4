"""TPC-DS query 3 (queries/q03.py) as Spark SQL plans it with adaptive
query execution on: the same physical plan under an AdaptiveSparkPlanExec
root that carries the session's AQE settings (the configuration's
`settings.aqe`), so the runner coalesces each stage's shuffle partitions
into ranges read by one task each. Scan columns, result order and the
reference are q03's.
"""

from __future__ import annotations

import importlib.util
import os

# loaded by path, as harness/registry.py loads a query
_spec = importlib.util.spec_from_file_location(
    "bench_queries_q03_for_aqe",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "q03.py"))
q03 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(q03)

SCAN_COLUMNS = q03.SCAN_COLUMNS
ORDER_KEYS = q03.ORDER_KEYS
reference = q03.reference


def plan(paths: dict, config: dict, params: dict):
    from blaze_tpu.spark import plan_model as P

    return P.adaptive(q03.plan(paths, config, params),
                      **config["settings"]["aqe"])
