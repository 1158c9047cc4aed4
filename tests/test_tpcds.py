"""TPC-DS q01-q10 catalogue (spark/tpcds.py): every cell of
`tpcds.QUERIES` x join mode (19) at a small row count, each through the
validator's cell runner: equal to its pandas oracle, and not served by a
fallback. The ten broadcast-mode cells are here, the nine sort-merge
ones in test_tpcds_smj.py (a file is one xdist worker's load).

`python validate.py --suite tpcds` runs the same matrix at 2M+ rows on
the chip. Among the shapes: correlated-subquery-as-join (q01), channel
union (q02), the benchmark's own query (q03), rollup via Expand (q05),
CASE-filtered global aggs (q09), EXISTS lattice (q10).
"""

import pytest

from blaze_tpu.spark import tpcds
from blaze_tpu.spark.validator import matrix_cells, run_cell

CELLS = matrix_cells("tpcds")


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tpcds")
    return tpcds.generate_tables(str(tmp), rows=6000)


def test_catalogue_has_19_cells():
    assert len(CELLS) == 19 == len(list(tpcds.warm_cells()))


def check_cell(tables, name, mode):
    r = run_cell(*tables, name, mode, suite="tpcds")
    assert r.ok, f"{name}/{mode}: {r.diff or ''} {r.error or ''}"


# q01 doubles as the broadcast-over-shuffled-agg regression (the
# broadcast stage must read ALL upstream partitions)
@pytest.mark.parametrize("name", [n for n, m in CELLS if m == "bhj"])
def test_tpcds_query(tables, name):
    check_cell(tables, name, "bhj")
