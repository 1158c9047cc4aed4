"""Test harness: force an 8-virtual-device CPU platform before jax loads.

Mirrors the reference's JNI-free unit-test strategy (SURVEY.md §4: operators
run with MemoryExec fakes and tempfile spills, no JVM): here operators run on
a virtual 8-device CPU mesh, no TPU required. Bench and the driver's
compile-check run on real hardware separately.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


# Every program jax compiles for the CPU backend stays mapped (three mappings
# an executable, one set a virtual device) for as long as its jitted function
# caches it, and a process may hold vm.max_map_count (65,530) mappings: past
# it jaxlib segfaults inside a compile. The worker that runs test_tpcds_smj.py
# in a whole six-worker run was read at 59,618 (the parent of PR 35) and
# 64,745 mappings near the file's end, and two of five such runs lost that
# worker there. So a worker past _MAP_BUDGET drops jax's caches between two
# tests: what is still needed compiles again.
_MAP_BUDGET = 40_000


@pytest.fixture(autouse=True)
def _bounded_mappings():
    yield
    try:
        with open("/proc/self/maps") as fh:
            mapped = sum(1 for _ in fh)
    except OSError:   # no procfs: nothing to read, nothing to bound
        return
    if mapped > _MAP_BUDGET:
        import gc

        import jax

        jax.clear_caches()
        gc.collect()


# xdist's loadfile hands files to its workers in collection order, so a long
# file late in the alphabet (test_tpcds_smj.py: minutes) ends alone on one
# worker after the others have drained; the longest files go out first.
_LONGEST_FIRST = ("test_tpcds_smj.py", "test_tpcds.py", "test_join.py",
                  "test_mesh_resident.py", "test_validator.py",
                  "test_compile_service.py", "test_spark_planner.py")


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(_LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    """A file's tests stay on one worker whatever `--dist` says. The order
    above is made for that: dealt out test by test (`--dist load`) it puts
    the TPC-DS files and test_join.py into one process, which then
    segfaults inside jaxlib's CPU compile at one of test_join.py's joins
    (five whole runs of five, the parent commit's too), and every
    module-scoped fixture is built once a worker instead of once."""
    from xdist.scheduler import LoadFileScheduling

    return LoadFileScheduling(config, log)

