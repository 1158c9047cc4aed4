#!/usr/bin/env python
"""Query-level correctness gate (the reference's TPC-DS validator analog).

Runs the BASELINE config query shapes through the full driver path
(tagging -> conversion -> stage splitting -> multi-stage execution) against
pandas goldens, across both join configs (BHJ and forced SMJ — the
reference's autoBroadcastJoinThreshold=-1 axis, tpcds.yml:131-147).

    python validate.py [--rows N] [--queries q3_join_agg_sort,...]

Runs on whatever platform jax resolves (JAX_PLATFORMS=cpu for the CPU
mesh). A cell passes only if its result equals the oracle AND its
run_info shows no fallback served it (validator.fallback_evidence) —
unless a fault spec or --spill-budget asked for one.

Exit code 0 iff every (query, join-mode) cell passes.
"""

import argparse
import os
import sys
import tempfile


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=20_000,
                    help="store_sales row count")
    ap.add_argument("--queries", type=str, default="",
                    help="comma-separated subset of query names")
    ap.add_argument("--spill-budget", type=int, default=0,
                    help="force-spill mode: MemManager byte budget per "
                    "cell (e.g. 2000000 with --rows 2000000 makes every "
                    "sort/agg/shuffle spill in query context)")
    ap.add_argument("--json-out", type=str, default="",
                    help="also write the per-cell results as JSON")
    ap.add_argument("--suite", type=str, default="core",
                    choices=["core", "tpcds", "all"],
                    help="core = BASELINE config shapes; tpcds = the "
                    "hand-constructed TPC-DS q01-q10 catalogue")
    args = ap.parse_args()

    from blaze_tpu.spark.validator import print_report, run_matrix

    queries = [q for q in args.queries.split(",") if q] or None
    suites = (["core", "tpcds"] if args.suite == "all" else [args.suite])
    results = []
    with tempfile.TemporaryDirectory(prefix="blaze_tpu_validate_") as tmp:
        for suite in suites:
            os.makedirs(f"{tmp}/{suite}", exist_ok=True)
            results += run_matrix(f"{tmp}/{suite}", rows=args.rows,
                                  queries=queries,
                                  spill_budget=args.spill_budget or None,
                                  suite=suite)
    ok = print_report(results)
    if args.json_out:
        import dataclasses
        import json

        with open(args.json_out, "w") as f:
            json.dump({"rows": args.rows,
                       "spill_budget": args.spill_budget,
                       "results": [dataclasses.asdict(r) for r in results]},
                      f, indent=1)
    if args.spill_budget and ok and not any(r.spill_count for r in results):
        print("FORCE-SPILL MODE: no spill observed — budget too large?")
        return 1
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
