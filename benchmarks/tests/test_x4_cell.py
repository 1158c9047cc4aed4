"""The four-chip cell rehearsed on four virtual devices (the conftest's
XLA_FLAGS): the run's last line is `correct`, every exchange rode four
devices, and the cell's three own per-layer metrics are on it. A rehearsal's
numbers are the CPU's: only their presence is checked, and the device's
share of them stays out. No child process."""

import json
import time

import pytest

from harness import loop

CELL = "sf1_q03_nobhj_x4"


@pytest.fixture(scope="module")
def rehearsal():
    import jax

    from blaze_tpu.config import conf

    if len(jax.devices()) != 4:
        pytest.skip("the cell asks for exactly four devices")
    traced = conf.trace_enabled
    lines = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("builtins.print",
                   lambda *a, **k: lines.append(" ".join(map(str, a))))
        rc = loop.run(CELL, seed=2100000011, seconds=2.0, traced=True,
                      rehearse_rows=200_000, t_start=time.perf_counter())
    conf.trace_enabled = traced
    assert rc == 0
    return lines


def test_the_rehearsal_is_correct_on_four_devices(rehearsal):
    line = json.loads(rehearsal[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4        # a window's query and three profiled
    assert line["device"]["count"] == 4 and line["device"]["platform"] == "cpu"
    counters = [json.loads(ln.split("counters: ", 1)[1]) for ln in rehearsal
                if ln.startswith("[bench] last query's counters: ")]
    assert counters == [{**counters[0], "mesh_stages": 5, "mesh_devices": 4,
                         "file_stages": 0, "spill_count": 0,
                         "compile_compile_count": 0}]
    kinds = json.loads([ln for ln in rehearsal if "first-call seconds" in ln]
                       [0].split("): ", 1)[1])
    assert "mesh_xchg" in kinds and "local_xchg" not in kinds


def test_the_cells_own_metrics_are_on_the_traced_line(rehearsal):
    metrics = json.loads(rehearsal[-1])["metrics"]
    assert metrics["mesh_exchange_s"]["value"] > 0
    assert metrics["mesh_exchange_s"]["unit"] == "s"
    # every exchanged partition was consumed on its chip
    assert metrics["mesh_host_roundtrip_MB"] == {"value": 0.0, "unit": "MB"}
    assert metrics["compiles_in_window"]["value"] == 0
    # device metrics are no CPU's to give; exchange_s is the one-chip cells'
    for name in ("chip_busy_balance", "device_idle_share",
                 "hbm_roofline_share", "peak_hbm_GB", "exchange_s"):
        assert name not in metrics
