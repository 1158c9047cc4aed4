"""The nine sort-merge cells of the TPC-DS catalogue (see test_tpcds.py,
which holds the broadcast ones and the shared cell runner)."""

import pytest

from test_tpcds import CELLS, check_cell, tables  # noqa: F401 — fixture


@pytest.mark.parametrize("name", [n for n, m in CELLS if m == "smj"])
def test_tpcds_query_smj(tables, name):
    check_cell(tables, name, "smj")
