"""Where a map stage's seconds go: waits, dispatches, the exchange, the host.

Runs one cell of BENCHMARK.json as benchmarks/run.py does (the same tables,
warm-up and `run_plan` call; on the chip, or on the CPU with
`JAX_PLATFORMS=cpu` and `--rehearse-rows N`) with `conf.trace_enabled` on, and
accounts each shuffle_map stage of each query from its span records:

  * every instant of a `stage` span goes to the innermost span open on the
    stage's thread that carries its `stage_id` (a `wait` inside an `exchange`
    is wait; the exchange keeps its remainder), or to `self` where none is:
    wait_ready + wait_blocked + dispatch + exchange + <other kinds> + self
    equals the stage's `dur`, nothing twice and nothing left out;
  * the `wait` sites by summed time, with pulls and blocking pulls
    (`ready` false).

`--compare 1` runs a query with tracing off beside each traced one (off on, on
off, ...) and reports both medians: what tracing costs, inside one process;
`pull_cost_us` is what one pull of a value the host holds costs, off and on. `--profile 1` runs one more query under `jax.profiler.trace` and names the
device's longest idle gaps inside each stage by the innermost `blaze:*`
annotation open on /host:CPU where the gap starts (a `blaze:wait` carries its
`site`). The report goes to stdout and `chiprun_out/wait_account_<cell>.json`
(`..._<cell>_rehearsal.json` off the chip), stamped with the platform and the
device kind it ran on.

    chiprun -- python3 tools/wait_account.py --workload sf10_q03_nobhj \
        --seed 123456789 --queries 3 --profile 1

A reading for PERF.md section 5, not a metric: the benchmark's four readers
(benchmarks/metrics/host_wait_s.py and its neighbours) read the same spans.
What covers a stage is defined by benchmarks/metrics/stage_self_share.py
(same `stage_id`, the stage's thread, every kind but CONTAINERS, clipped);
this tool only labels that cover piece by piece, and tests/test_wait_spans.py
holds its `self` to the reader's on the same records.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import statistics
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTAINERS = ("stage", "task_attempt")


def innermost_segments(intervals: list, t0: int, t1: int,
                       uncovered: str = "self") -> list:
    """`intervals`: (start, end, label) of spans of ONE thread, so properly
    nested. [t0, t1) cut into (start, end, label) pieces, each with the label
    of the innermost interval open there, or `uncovered`."""
    pieces: list = []
    stack: list = []  # (end, label) of the open intervals, outermost first
    now = t0

    def advance(to: int) -> None:
        nonlocal now
        to = min(max(to, now), t1)
        if to > now:
            pieces.append((now, to, stack[-1][1] if stack else uncovered))
        now = to

    for a, b, label in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        if b <= t0 or a >= t1:
            continue
        while stack and stack[-1][0] <= a:
            advance(stack[-1][0])
            stack.pop()
        advance(a)
        stack.append((b, label))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    advance(t1)
    return pieces


def innermost_parts(intervals: list, t0: int, t1: int,
                    uncovered: str = "self") -> dict:
    """The pieces' lengths by label: the values sum to t1 - t0."""
    parts: dict = {}
    for a, b, label in innermost_segments(intervals, t0, t1, uncovered):
        parts[label] = parts.get(label, 0) + (b - a)
    return parts


def _label(span: dict) -> str:
    if span["kind"] != "wait":
        return span["kind"]
    return ("wait_ready" if span.get("attrs", {}).get("ready")
            else "wait_blocked")


def stage_account(spans: list) -> list:
    """One dict per shuffle_map `stage` span of a query's span records, in
    start order: its seconds by the innermost covering span's kind."""
    out = []
    for st in sorted((s for s in spans if s["kind"] == "stage"
                      and s.get("attrs", {}).get("stage_kind")
                      == "shuffle_map" and s.get("dur")),
                     key=lambda s: s["ts"]):
        t0, t1 = st["ts"], st["ts"] + st["dur"]
        inner = [s for s in spans if s["kind"] not in CONTAINERS
                 and s.get("stage_id") == st.get("stage_id")
                 and s.get("thread") == st.get("thread")
                 and s["ts"] < t1 and s["ts"] + s["dur"] > t0]
        parts = innermost_parts(
            [(s["ts"], s["ts"] + s["dur"], _label(s)) for s in inner], t0, t1)
        programs: dict = {}
        for s in inner:
            if s["kind"] == "dispatch":
                p = s.get("attrs", {}).get("program", "?")
                programs[p] = programs.get(p, 0) + 1
        waits = [s for s in inner if s["kind"] == "wait"]
        out.append({
            "stage_id": st.get("stage_id"), "dur_s": st["dur"] / 1e9,
            "parts_s": {k: v / 1e9 for k, v in sorted(parts.items())},
            "pulls": len(waits),
            "blocking": sum(1 for s in waits
                            if not s.get("attrs", {}).get("ready")),
            "dispatches": sum(programs.values()), "programs": programs,
            "sites": wait_sites(waits),
        })
    return out


def wait_sites(spans: list) -> list:
    """[site, seconds, seconds found ready, pulls, blocking pulls], the
    heaviest first."""
    sites: dict = {}
    for s in spans:
        if s["kind"] != "wait":
            continue
        a = s.get("attrs", {})
        row = sites.setdefault(a.get("site", "?"), [0, 0, 0, 0])
        row[0] += s["dur"]
        row[1] += s["dur"] if a.get("ready") else 0
        row[2] += 1
        row[3] += 0 if a.get("ready") else 1
    return [[site, r[0] / 1e9, r[1] / 1e9, r[2], r[3]] for site, r in
            sorted(sites.items(), key=lambda kv: -kv[1][0])]


BETWEEN_NS = 100_000
NOT_AN_ACTIVITY = ("blaze:stage", "blaze:query", "blaze:profile",
                   "blaze:task_attempt")


def idle_gaps(log_dir: str, top: int = 5) -> dict:
    """Per `blaze:stage` annotation of a profiler trace (in start order):
    the device's idle gaps that start inside it, by the innermost `blaze:*`
    annotation open on the stage's host thread (a `blaze:wait` by its
    `site`; `(none)` where the host is in no span), the longest gaps one by
    one, and `top` gaps of 0.1 ms or more in turn from the middle of the
    stage. The device's clock may run a millisecond off the host's
    (benchmarks/trace_reduce.py), so a gap's edges are that uncertain."""
    from jax.profiler import ProfileData

    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    import trace_reduce

    data = ProfileData.from_file(trace_reduce.find_xplane(log_dir))
    busy, report = [], {}
    for plane in data.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    busy += [(s, e) for s, e, _ in
                             trace_reduce._intervals(line)]
    busy = trace_reduce._merge(busy)
    gaps = [(b0[1], b1[0]) for b0, b1 in zip(busy, busy[1:])]
    for plane in data.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            notes, stages = [], []
            for ev in line.events:
                if not ev.name.startswith("blaze:"):
                    continue
                stats = dict(ev.stats)
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == "blaze:stage":
                    stages.append(iv + (stats.get("stage_id"),))
                elif ev.name not in NOT_AN_ACTIVITY:
                    site = stats.get("site")
                    notes.append(iv + (
                        ev.name + (f"[{site}]" if site else ""),))
            for s0, s1, stage_id in sorted(stages):
                pieces = innermost_segments(notes, s0, s1, "(none)")
                starts = [p[0] for p in pieces]

                def host_ms(g0: int, g1: int) -> dict:
                    found: dict = {}
                    i = max(bisect.bisect_right(starts, g0) - 1, 0)
                    while i < len(pieces) and pieces[i][0] < g1:
                        a, b, label = pieces[i]
                        over = min(b, g1) - max(a, g0)
                        if over > 0:
                            found[label] = found.get(label, 0) + over / 1e6
                        i += 1
                    return found

                inside = [g for g in gaps if s0 <= g[0] < s1]
                by_note: dict = {}
                for g0, g1 in inside:
                    for k, v in host_ms(g0, g1).items():
                        by_note[k] = by_note.get(k, 0) + v
                def rows(some: list) -> list:
                    return [{"gap_ms": (g1 - g0) / 1e6,
                             "at_ms_into_stage": (g0 - s0) / 1e6,
                             "host_ms": host_ms(g0, g1)} for g0, g1 in some]

                # gaps between programs; shorter ones lie inside a program
                between = [g for g in inside if g[1] - g[0] >= BETWEEN_NS]
                mid = len(between) // 2
                report[f"stage {stage_id} at {s0}"] = {
                    "stage_ms": (s1 - s0) / 1e6, "gaps": len(inside),
                    "idle_ms": sum(b - a for a, b in inside) / 1e6,
                    "gaps_between_programs": len(between),
                    "idle_ms_by_annotation": dict(sorted(
                        by_note.items(), key=lambda kv: -kv[1])),
                    "longest": rows(sorted(
                        inside, key=lambda g: g[0] - g[1])[:top]),
                    "mid_stage_in_turn": rows(between[mid:mid + top])}
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--queries", type=int, default=3)
    ap.add_argument("--rehearse-rows", type=int, default=0)
    ap.add_argument("--profile", type=int, default=0)
    ap.add_argument("--compare", type=int, default=0,
                    help="1: before each traced query one with tracing off")
    args = ap.parse_args()

    sys.path.insert(0, REPO)
    sys.path.insert(0, os.path.join(REPO, "benchmarks"))
    from harness import loop, traffic
    from harness.registry import Registry

    rehearsal = loop._select_platform(args.rehearse_rows)
    reg = Registry()
    entry = reg.cell(args.workload)
    loop._build_native(REPO)

    import jax

    from blaze_tpu.config import conf

    report = {"workload": args.workload, "seed": args.seed,
              "device": loop._device(entry["chips"], rehearsal),
              "rehearse_rows": args.rehearse_rows, "queries": []}
    with tempfile.TemporaryDirectory(prefix="blaze_wait_") as tmp:
        cell = loop.Cell(reg, entry, args.seed, tmp, args.rehearse_rows)
        draws = traffic.schedule(cell.traffic, args.seed, loop.WARMUP_STREAM)
        for _ in range(2):
            cell.run_query(*next(draws))
        untraced = []
        report["pull_cost_us"] = pull_cost_us()

        def plain() -> None:
            conf.update(trace_enabled=False)
            untraced.append(cell.run_query(*next(draws))["seconds"])

        for i in range(args.queries):
            # off on | on off | ...: no arm is always first of its pair
            if args.compare and i % 2 == 0:
                plain()
            conf.update(trace_enabled=True)
            q = cell.run_query(*next(draws))
            if args.compare and i % 2 == 1:
                plain()
            records = sum(1 for _ in q["spans"])
            report["queries"].append({
                "seconds": q["seconds"], "refused": q["refused"],
                "span_records": records,
                "spans_by_kind": _by_kind(q["spans"]),
                "stages": stage_account(q["spans"]),
                "sites": wait_sites(q["spans"])[:10]})
        if args.profile and not rehearsal:
            import trace_reduce

            log_dir = os.path.join(tmp, "profile")
            with jax.profiler.trace(
                    log_dir,
                    profiler_options=trace_reduce.profile_options()):
                cell.run_query(*next(draws))
            report["idle_gaps"] = idle_gaps(log_dir)
    secs = [q["seconds"] for q in report["queries"]]
    report["median_query_s"] = statistics.median(secs)
    if untraced:
        report["untraced_seconds"] = untraced
        report["median_untraced_query_s"] = statistics.median(untraced)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    name = f"wait_account_{args.workload}"
    if rehearsal:
        name += "_rehearsal"
    with open(os.path.join(out_dir, name + ".json"), "w") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps(report))
    return 0


def pull_cost_us(n: int = 20000) -> dict:
    """Microseconds a `pull_rows` of a value the host already holds costs,
    tracing off and on: the seam's and the span's own cost, no device in it."""
    import time

    import numpy as np

    from blaze_tpu.columnar.batch import ColumnBatch, pull_rows
    from blaze_tpu.columnar.types import INT64, Field, Schema
    from blaze_tpu.config import conf
    from blaze_tpu.runtime import trace

    batch = ColumnBatch.from_numpy({"a": np.arange(8)},
                                   Schema([Field("a", INT64)]))
    int(batch.num_rows)

    def each(fn) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        return (time.perf_counter() - t0) / n * 1e6

    saved = conf.trace_enabled
    cost = {"plain_int": each(lambda: int(batch.num_rows))}
    for label, on in (("off", False), ("on", True)):
        conf.update(trace_enabled=on)
        with trace.span("stage", stage_id=0, query_id="cost"):
            cost[label] = each(lambda: pull_rows(batch, "op.output_rows"))
    conf.update(trace_enabled=saved)
    trace.reset()
    return cost


def _by_kind(spans: list) -> dict:
    kinds: dict = {}
    for s in spans:
        kinds[s["kind"]] = kinds.get(s["kind"], 0) + 1
    return dict(sorted(kinds.items()))


if __name__ == "__main__":
    sys.exit(main())
