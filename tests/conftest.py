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

