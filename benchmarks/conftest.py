"""One accepted case reads its manifest entry by position:
tests/test_z_filter_mask_share.py::test_the_entry_names_the_cells_that_filter
takes `per_layer[-1]` for `filter_mask_share`. New entries go at the end of
their lists and no PR but a `benchmark` one may edit a file the benchmark has,
so since PR 34 appended three entries that case cannot pass as written. It is
marked an expected failure here (an AssertionError and nothing else), and
tests/test_zz_sf10_nobhj_cell.py::test_filter_mask_share_is_found_by_name
makes every one of its assertions on the entry found by name. The `benchmark`
PR that mends the case (one line: find the entry by name) deletes this file
and that twin."""
import pytest

BY_POSITION = ("test_z_filter_mask_share.py"
               "::test_the_entry_names_the_cells_that_filter")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(BY_POSITION):
            item.add_marker(pytest.mark.xfail(
                raises=AssertionError, strict=False,
                reason="reads per_layer[-1]; PR 34 appended entries after "
                       "it: asserted by name in test_zz_sf10_nobhj_cell.py"))
