#!/usr/bin/env python3
"""One run of one benchmark cell (benchmarks/README.md):

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs the chips the cell asks for; with JAX_PLATFORMS=cpu already in the
environment AND --rehearse-rows N it rehearses the same loop on the CPU at N
fact rows and names `cpu` as its device.
"""
import time

T_START = time.perf_counter()   # set-up counts from here

import argparse   # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-rows", type=int, default=0)
    args = ap.parse_args()

    from harness import loop

    return loop.run(args.workload, args.seed, args.seconds, bool(args.trace),
                    args.rehearse_rows, T_START)


if __name__ == "__main__":
    sys.exit(main())
