#!/usr/bin/env python3
"""How fixtures/tiny_v5e.xplane.pb was recorded (PR 24, one v5e chip):

    chiprun -- python3 benchmarks/fixtures/record.py

Three annotated steps of one small jitted program with host sleeps between
them, traced with the options the harness uses, then a plain-text dump of
every plane and line so the reducer is written against what the profiler
really emits. Not run by the benchmark.
"""
import glob
import os
import shutil
import sys
import time

os.environ["JAX_PLATFORMS"] = "tpu,cpu"
import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
from trace_reduce import profile_options  # noqa: E402

out = os.path.join(os.getcwd(), "chiprun_out", "fixture")
shutil.rmtree(out, ignore_errors=True)
os.makedirs(out)
print("env JAX_COMPILATION_CACHE_DIR =",
      os.environ.get("JAX_COMPILATION_CACHE_DIR"), "TMPDIR =",
      os.environ.get("TMPDIR"), "HOME =", os.environ.get("HOME"))
print(jax.devices(), jax.devices()[0].memory_stats())

f = jax.jit(lambda x: jnp.sin(x @ x).sum())
x = jnp.ones((1024, 1024), jnp.float32)
f(x).block_until_ready()
with jax.profiler.trace(out, profiler_options=profile_options()):
    for i in range(3):
        with jax.profiler.TraceAnnotation(f"bench_query_{i}"):
            f(x).block_until_ready()
            time.sleep(0.02)
        time.sleep(0.01)
pb = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
shutil.copy(pb, os.path.join(out, "tiny_v5e.xplane.pb"))
shutil.rmtree(os.path.join(out, "plugins"))
data = jax.profiler.ProfileData.from_file(os.path.join(out, "tiny_v5e.xplane.pb"))
with open(os.path.join(out, "dump.txt"), "w") as fh:
    for plane in data.planes:
        print("PLANE", repr(plane.name), file=fh)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs), file=fh)
            for ev in evs[:40]:
                print("    ", repr(ev.name), ev.start_ns, ev.duration_ns,
                      file=fh)
print(open(os.path.join(out, "dump.txt")).read()[-6000:])
print("size", os.path.getsize(os.path.join(out, "tiny_v5e.xplane.pb")))
