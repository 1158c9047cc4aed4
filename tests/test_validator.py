"""The query-level validator matrix as a test (the reference's TPC-DS CI
gate analog, .github/workflows/tpcds.yml:92-147). `python validate.py`
runs the same matrix standalone with bigger data."""

import pytest

from blaze_tpu.spark.validator import (QUERIES, _JOINLESS, generate_tables,
                                       matrix_cells, run_cell, run_matrix)


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    return generate_tables(str(tmp_path_factory.mktemp("core")), rows=4000)


def test_validator_matrix_cell_count():
    expected_cells = sum(1 if q in _JOINLESS else 2 for q in QUERIES)
    assert len(matrix_cells()) == expected_cells == 15


@pytest.mark.parametrize("name,mode", matrix_cells())
def test_validator_matrix(tables, name, mode):
    r = run_cell(*tables, name, mode)
    assert r.ok, f"{r.query}[{r.mode}]: {r.diff or ''} {r.error or ''}"


def test_cell_served_by_a_fallback_fails(tmp_path, monkeypatch):
    """An oracle-equal answer is not a PASS when run_info says the ladder,
    a retry or the row interpreter produced it — unless the cell asked for
    faults or forced spill."""
    from blaze_tpu.spark import validator

    real = validator.run_plan

    def degraded(*a, run_info, **kw):
        out = real(*a, run_info=run_info, **kw)
        run_info["ladder_rung"] = 3
        run_info["bytes_copied_fallback"] = 4096
        return out

    monkeypatch.setattr(validator, "run_plan", degraded)
    (cell,) = run_matrix(str(tmp_path), rows=1500,
                         queries=["q1_scan_filter_project"])
    assert not cell.ok and "served by a fallback" in cell.diff
    assert cell.run_info["ladder_rung"] == 3
    (spilled,) = run_matrix(str(tmp_path), rows=1500,
                            queries=["q1_scan_filter_project"],
                            spill_budget=1 << 30)
    assert spilled.ok
