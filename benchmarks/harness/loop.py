"""One run of one cell: set-up, warm-up, the closed loop for `seconds`, the
comparison of every result, and with --trace 1 three profiled queries after
the window. Names no cell, configuration, query or metric: everything comes
from the manifest and the files it names (harness/registry.py).
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import statistics
import subprocess
import tempfile
import threading
import time

from harness import compare, contract, evidence, traffic
from harness.registry import Registry

TRACED_QUERIES = 3
MARK = "bench_query_"
# draws for the warm-up and the profiled queries come from streams of their
# own, so they never shift a client's schedule
PROFILED_STREAM, WARMUP_STREAM = 10 ** 6, 10 ** 6 + 1


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def _select_platform(rehearse_rows: int) -> bool:
    """True for a CPU rehearsal. A measurement pins the platform BEFORE jax
    loads: every listed platform must initialize, so a missing chip raises;
    `cpu` stays listed because jax.pure_callback needs a CPU device."""
    on_cpu = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
    if bool(rehearse_rows) != on_cpu:
        raise SystemExit(
            "a rehearsal needs both JAX_PLATFORMS=cpu in the environment "
            "and --rehearse-rows N; a measurement takes neither")
    if not on_cpu:
        os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    return on_cpu


def _build_native(repo: str) -> None:
    """The C++ serde/shuffle layer is git-ignored: build it where absent."""
    if not os.path.exists(os.path.join(
            repo, "native", "libblaze_tpu_native.so")):
        subprocess.run(["make", "-C", os.path.join(repo, "native")],
                       check=True, stdout=subprocess.DEVNULL)


def _device(chips: int, rehearsal: bool) -> dict:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"jax {jax.__version__} device {json.dumps(device)}")
    if not rehearsal and device["platform"] != "tpu":
        raise SystemExit(f"platform is {device['platform']}, not tpu")
    if device["count"] != chips:
        # the program spreads over every device it sees, so more is as
        # wrong as fewer
        raise SystemExit(f"the cell asks for {chips} chip(s), jax sees "
                         f"{device['count']}")
    return device


def _memory_peak():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    return max(peaks) if all(p is not None for p in peaks) else None


class Cell:
    """The cell's tables, queries and references, and how one query runs."""

    def __init__(self, reg: Registry, cell: dict, seed: int, tmp: str,
                 rehearse_rows: int):
        self.cell = cell
        self.config = reg.data("configs", cell["config"])
        self.traffic = reg.data("traffic", cell["traffic"])
        self.seed = seed
        t0 = time.perf_counter()
        generator = reg.module("datagen", self.config["generator"])
        self.paths, self.frames = generator.generate(
            self.config, seed, tmp, rehearse_rows or None)
        self.rows = {k: len(v) for k, v in self.frames.items()}
        say(f"tables from seed {seed} in {time.perf_counter() - t0:.1f}s: "
            + json.dumps(self.rows))
        self.queries = {e["query"]: reg.module("queries", e["query"])
                        for e in self.traffic["mix"]}
        self._references = {}
        self._lock = threading.Lock()

    def scan_bytes(self, query: str) -> int:
        return sum(self.rows[table] * sum(widths.values()) for table, widths
                   in self.queries[query].SCAN_COLUMNS.items())

    def reference(self, query: str, params: dict):
        key = (query, json.dumps(params, sort_keys=True))
        with self._lock:
            if key not in self._references:
                t0 = time.perf_counter()
                self._references[key] = self.queries[query].reference(
                    self.frames, self.config, params)
                say(f"reference {key[0]} {key[1]}: "
                    f"{len(self._references[key])} rows in "
                    f"{time.perf_counter() - t0:.2f}s")
            return self._references[key]

    def run_query(self, query: str, params: dict, mark: str = None) -> dict:
        """One query: a fresh plan (plans are single-use) handed to
        run_plan -> result frame on the host, on the caller's clock; the
        comparison and the evidence are read outside the interval."""
        import jax

        from blaze_tpu.config import conf
        from blaze_tpu.runtime import trace
        from blaze_tpu.spark.local_runner import run_plan

        settings = self.config["settings"]
        plan = self.queries[query].plan(self.paths, self.config, params)
        want = self.reference(query, params)
        run_info: dict = {}
        annotation = (jax.profiler.TraceAnnotation(mark) if mark
                      else contextlib.nullcontext())
        t0 = time.perf_counter()
        with annotation:
            got = compare.to_frame(run_plan(
                plan, num_partitions=settings["exchange_width"],
                mesh_exchange=settings["mesh_exchange"], run_info=run_info))
        seconds = time.perf_counter() - t0
        why = evidence.refusals(run_info, self.cell["chips"],
                                settings["exchange_width"])
        wrong = compare.diff(got, want,
                             self.config["guarantees"]["float_rtol"],
                             self.queries[query].ORDER_KEYS)
        if wrong:
            why.append(f"differs from the reference: {wrong}")
        spans = None
        if conf.trace_enabled:   # read now: the ring is bounded
            spans = [r for r in trace.query_records(run_info["query_id"])
                     if r.get("type") == "span"]
        return {"query": query, "seconds": seconds, "refused": why,
                "spans": spans, "counters": {k: run_info.get(k, 0) for k in (
                    "mesh_stages", "mesh_devices", "file_stages",
                    "broadcast_stages", "spill_count",
                    "compile_compile_count")}}


def _closed_loop(cell: Cell, seconds: float) -> list:
    """Each client starts its next query when the last is on the host and
    compared; a query that starts inside the window is finished and counts."""
    clients = int(cell.traffic.get("clients", 1))
    done, deadline = [[] for _ in range(clients)], time.perf_counter() + seconds

    def client(index: int) -> None:
        for query, params in traffic.schedule(cell.traffic, cell.seed, index):
            if time.perf_counter() >= deadline:
                return
            done[index].append(cell.run_query(query, params))

    # the first client is this thread: a loop of one starts no thread
    others = [threading.Thread(target=client, args=(i,), daemon=True)
              for i in range(1, clients)]
    for t in others:
        t.start()
    client(0)
    for t in others:
        t.join()
    return [q for per_client in done for q in per_client]


def _traced_queries(cell: Cell, tmp: str, rehearsal: bool):
    """Three consecutive queries under the profiler, each inside a host
    annotation; the device reduction is None on a rehearsal (a CPU trace has
    no device plane, and a CPU number is no device metric)."""
    import jax

    import trace_reduce

    draws = traffic.schedule(cell.traffic, cell.seed, PROFILED_STREAM)
    log_dir = os.path.join(tmp, "profile")
    with jax.profiler.trace(
            log_dir, profiler_options=trace_reduce.profile_options()):
        queries = [cell.run_query(*next(draws), mark=f"{MARK}{i}")
                   for i in range(TRACED_QUERIES)]
    if rehearsal:
        return queries, None
    t0 = time.perf_counter()
    reduction = trace_reduce.reduce_trace(log_dir, MARK)
    say(f"trace reduced in {time.perf_counter() - t0:.1f}s: per device "
        + json.dumps(reduction["per_device"]))
    return queries, reduction


def run(workload: str, seed: int, seconds: float, traced: bool,
        rehearse_rows: int, t_start: float) -> int:
    rehearsal = _select_platform(rehearse_rows)
    reg = Registry()
    cell_entry = reg.cell(workload)
    _build_native(os.path.dirname(reg.dir))

    import jax

    from blaze_tpu.config import conf
    from blaze_tpu.runtime import compile_service, memory

    device = _device(cell_entry["chips"], rehearsal)
    peaks = None if rehearsal else reg.peaks(device["kind"])
    if conf.executor_count or conf.fault_injection_spec:
        raise SystemExit("executor_count / a fault spec is set: pool "
                         "workers cannot share the chip, faults are no cell")
    conf.update(trace_enabled=traced)
    say(f"compile cache {jax.config.jax_compilation_cache_dir}; memory "
        f"budget {memory.get_manager().total} bytes")

    tmp = tempfile.mkdtemp(prefix="blaze_bench_")
    try:
        cell = Cell(reg, cell_entry, seed, tmp, rehearse_rows)
        # warm-up: each query of the mix twice, nothing else
        warm_first = None
        for entry in cell.traffic["mix"]:
            draws = traffic.schedule({**cell.traffic, "mix": [entry]}, seed,
                                     WARMUP_STREAM)
            for _ in range(2):
                warm = cell.run_query(*next(draws))
                say(f"warm-up {warm['query']}: {warm['seconds']:.2f}s "
                    f"{json.dumps(warm['counters'])}")
                if warm_first is None:
                    warm_first = warm["seconds"]
        tel0 = compile_service.TELEMETRY.snapshot()
        setup_s = time.perf_counter() - t_start
        window = _closed_loop(cell, seconds)
        window_s = time.perf_counter() - t_start - setup_s
        tel1 = compile_service.TELEMETRY.snapshot()
        memory_peak = _memory_peak()
        profiled, reduction = (_traced_queries(cell, tmp, rehearsal)
                               if traced else ([], None))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    finished = window + profiled
    failed = [q for q in finished if q["refused"]]
    for q in failed[:5]:
        say(f"FAILED {q['query']}: {'; '.join(q['refused'])}")
    state = {
        "setup_s": setup_s, "warm_first_query_s": warm_first,
        "window": window, "profiled": profiled, "reduction": reduction,
        "telemetry": {k: v - tel0.get(k, 0) for k, v in tel1.items()
                      if isinstance(v, (int, float))},
        "memory_peak_bytes": memory_peak, "peaks": peaks,
        "chips": cell_entry["chips"],
        "scan_bytes": {q: cell.scan_bytes(q) for q in cell.queries},
    }
    metrics, group = {}, "per_layer" if traced else "end_to_end"
    for m in reg.metrics(workload, group):
        value = reg.module("metrics", m["name"]).read(state)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    times = [q["seconds"] for q in window]
    median = statistics.median(times)
    scanned = statistics.median(state["scan_bytes"][q["query"]]
                                for q in window)
    say(f"window {window_s:.2f}s: {len(window)} queries, seconds "
        f"min/median/max {min(times):.4f}/{median:.4f}/{max(times):.4f}; "
        f"input {scanned / 1e9 / median:.3f} GB/s of referenced columns at "
        "the median (a reading, it decides nothing)")
    say("telemetry over the window: " + json.dumps(
        {k: v for k, v in state["telemetry"].items() if v}, sort_keys=True))
    say("last query's counters: " + json.dumps(window[-1]["counters"]))
    say("first-call seconds by program kind (whole process): " + json.dumps(
        {k: round(v["compile_ns"] / 1e9, 2) for k, v in
         compile_service.registry().stats()["per_kind"].items()
         if v["compile_ns"] >= 5e7}, sort_keys=True))
    if rehearsal:
        say("REHEARSAL on the CPU: device idle share, roofline share and "
            "peak HBM are not measured; no time here is a device's")
    device["memory_peak_bytes"] = memory_peak
    if reduction:
        device.update(busy_s=reduction["busy_s"],
                      window_s=reduction["window_s"])
    print(contract.contract_line(
        not failed and bool(finished), len(finished), len(failed), metrics,
        device, reduction), flush=True)
    return 0
