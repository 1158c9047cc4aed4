"""From a `jax.profiler` trace to device busy/idle, top operations and idle
gaps. The benchmark's own reduction: nothing here imports the program.

What a v5e trace holds (read by hand from fixtures/tiny_v5e.xplane.pb, PR
24): one plane per chip named `/device:TPU:<n>` whose line `XLA Ops` has
one event per executed HLO operation (name = the HLO text, `%fusion.3 =
...`) and whose line `XLA Modules` has one event per executed program
(`jit_<fn>(<hash>)`, kept whole in an operation's name: most of the
program's modules are `jit_run`, and only the hash tells them apart); the
plane `/host:CPU` has a line per host thread, and a
`jax.profiler.TraceAnnotation` is an event there under its own name.
Event times are nanoseconds on one axis, but the device's clock read about
1.3 ms ahead of the host's in that trace, so a window cut from host
annotations is off by that much at each edge.

Busy is the union of the `XLA Ops` intervals of a device plane inside the
window; the window runs from the start of the first host annotation whose
name starts with `prefix` to the end of the last. Idle gaps are the
complement, each named by the annotation in which it starts.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE, HOST_PLANE = "XLA Ops", "XLA Modules", "/host:CPU"


def profile_options():
    """Host TraceMe events on (annotations need them), the Python tracer
    off: it would record every call of a multi-second query."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return options


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _intervals(line) -> list:
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in line.events]


def _merge(spans: list) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    merged = []
    for start, end in sorted(spans):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _short(op_name: str) -> str:
    """`%fusion.3 = f32[...] fusion(...)` -> `%fusion.3`."""
    return op_name.split(" = ", 1)[0][:60]


def _module_of(modules: list, starts: list, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][1] >= t:
        return modules[i][2]
    return "?"


def reduce_trace(path: str, prefix: str = "bench_query_") -> dict:
    """`path`: an .xplane.pb file, or a profiler log directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    devices, marks = {}, []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                devices[int(match.group(1))] = (
                    _intervals(lines[OPS_LINE]),
                    sorted(_intervals(lines[MODULES_LINE]))
                    if MODULES_LINE in lines else [])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                marks += [iv for iv in _intervals(line)
                          if iv[2].startswith(prefix)]
    if not marks:
        raise RuntimeError(f"no host annotation named {prefix}* in {path}")
    if not devices:
        raise RuntimeError(f"no /device:TPU:<n> plane with an "
                           f"'{OPS_LINE}' line in {path}")
    marks.sort()
    w0, w1 = marks[0][0], max(m[1] for m in marks)

    def mark_at(t: float) -> str:
        for start, end, name in marks:
            if start <= t < end:
                return name
        return "between_queries"

    per_device, op_time, gaps = [], {}, []
    for index in sorted(devices):
        ops, modules = devices[index]
        starts = [m[0] for m in modules]
        clipped = []
        for start, end, name in ops:
            s, e = max(start, w0), min(end, w1)
            if e > s:
                clipped.append((s, e))
                key = f"{_module_of(modules, starts, start)}/{_short(name)}"
                op_time[key] = op_time.get(key, 0.0) + (e - s) / 1e9
        busy = _merge(clipped)
        busy_s = sum(e - s for s, e in busy) / 1e9
        per_device.append({"device": index, "busy_s": busy_s,
                           "idle_share": 1.0 - busy_s / ((w1 - w0) / 1e9)})
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        suffix = f"@TPU:{index}" if len(devices) > 1 else ""
        gaps += [(mark_at(a) + suffix, (b - a) / 1e9)
                 for a, b in zip(edges[::2], edges[1::2]) if b > a]
    top = lambda pairs, n: [[k, v] for k, v in sorted(
        pairs, key=lambda kv: -kv[1])[:n]]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device) / len(per_device),
        "queries": len(marks),
        "per_device": per_device,
        "device_ops": top(op_time.items(), 10),
        "idle_gaps": top(gaps, 5),
    }
