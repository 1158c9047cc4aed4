"""The four readers of the program's `wait` spans (PR 36): `host_wait_s`,
`blocking_waits_per_query`, `wait_ready_share` and `stage_self_share` over
hand-made span lists, their entries in the manifest found by name, and
`sf1_q06core_agg` rehearsed on the CPU with the four on its traced line. A
rehearsal's numbers are the CPU's: presence is checked, never a time. No child
process. No position from the end of a list is asserted.

The file's name sorts it last, for test_y_decimal_cell.py's reason."""

import json

import pytest

from harness.registry import Registry

ENTRY = ("host_wait_s", "blocking_waits_per_query", "wait_ready_share")
STAGE = "stage_self_share"
ALL_CELLS = ["sf10_q03_bhj", "sf1_q06core_agg", "sf1_q03_nobhj",
             "sf1_q03_nobhj_x4", "sf10_q06core_agg", "sf1_q06core_agg_dec",
             "sf10_q03_nobhj"]
ONE_CHIP = [c for c in ALL_CELLS if c != "sf1_q03_nobhj_x4"]
MS = 10 ** 6


@pytest.fixture(scope="module")
def reg():
    return Registry()


def span(kind, ts, dur, stage_id=None, thread="MainThread", **attrs):
    span.n += 1
    rec = {"type": "span", "kind": kind, "id": span.n, "ts": ts * MS,
           "dur": dur * MS, "thread": thread, "attrs": attrs}
    if stage_id is not None:
        rec["stage_id"] = stage_id
    return rec


span.n = 0


def wait(ts, dur, ready, stage_id=0, thread="MainThread", site="join.x"):
    return span("wait", ts, dur, stage_id, thread, site=site, ready=ready)


def run_of(*queries):
    return {"window": [{"spans": q} for q in queries[:-1]],
            "profiled": [{"spans": queries[-1]}]}


def read(reg, name, *queries):
    return reg.module("metrics", name).read(run_of(*queries))


# one query: a map stage of 1,000 ms on the driver's thread
QUERY = [
    span("query", 0, 1200),
    span("stage", 100, 1000, 0, stage_kind="shuffle_map"),
    span("dispatch", 110, 40, 0, program="fused"),
    span("exchange", 200, 500, 0, transport="local"),
    span("dispatch", 210, 30, 0, program="local_xchg"),  # in the exchange
    wait(250, 400, False, site="exchange.local_bounds"),  # in the exchange
    wait(260, 100, True),            # nested in the wait above: once
    wait(750, 50, True),
    wait(760, 200, False, thread="pool-1"),   # another thread's
    span("h2d", 900, 50, 0, thread="prefetch-0", what="scan"),
    span("task_attempt", 100, 1000, 0),       # a container covers nothing
    span("stage", 1100, 50, 1, stage_kind="result"),
    wait(1110, 10, False, stage_id=1),        # another stage's
]


@pytest.mark.parametrize("name", ENTRY + (STAGE,))
def test_none_on_a_run_without_wait_spans(reg, name):
    old = [s for s in QUERY if s["kind"] != "wait"]
    assert read(reg, name, old, old) is None
    assert read(reg, name, [], []) is None
    assert reg.module("metrics", name).read(
        {"window": [{"spans": None}], "profiled": []}) is None   # trace 0


def test_host_wait_s_sums_every_threads_waits(reg):
    # 400 + 100 + 50 + 200 + 10 ms
    assert read(reg, "host_wait_s", QUERY) == pytest.approx(0.760)
    short = [span("query", 0, 100), wait(10, 20, True)]
    assert read(reg, "host_wait_s", QUERY, short, short) == pytest.approx(
        0.020)                                  # the median query's


def test_blocking_waits_counts_the_pulls_that_found_nothing_ready(reg):
    assert read(reg, "blocking_waits_per_query", QUERY) == 3
    all_ready = [span("query", 0, 100), wait(10, 20, True), wait(40, 5, True)]
    assert read(reg, "blocking_waits_per_query", all_ready) == 0
    assert read(reg, "blocking_waits_per_query", QUERY, all_ready) == 1.5


def test_wait_ready_share_weighs_by_time(reg):
    # ready 100 + 50 of 760 ms
    assert read(reg, "wait_ready_share", QUERY) == pytest.approx(
        100.0 * 150 / 760)
    # a hundred repeat pulls of a cached value weigh next to nothing
    cached = [wait(i, 0.001, True) for i in range(100)]
    q = [span("query", 0, 1000), wait(500, 99.9, False)] + cached
    assert read(reg, "wait_ready_share", q) == pytest.approx(0.1, rel=1e-3)
    zero = [span("query", 0, 10), wait(1, 0, True)]
    assert read(reg, "wait_ready_share", zero) is None    # nothing to share


def test_stage_self_share_is_what_no_span_of_the_stages_thread_covers(reg):
    # covered on MainThread in stage 0: dispatch 110-150, exchange 200-700
    # (with what is nested in it, once), wait 750-800: 590 of 1,000 ms;
    # the pool's wait, the prefetch thread's h2d, the task_attempt and the
    # result stage's wait cover nothing of it
    assert read(reg, STAGE, QUERY) == pytest.approx(41.0)
    # spans past the stage's end are clipped to it
    late = [span("stage", 0, 100, 0, stage_kind="shuffle_map"),
            wait(50, 500, False)]
    assert read(reg, STAGE, late) == pytest.approx(50.0)
    # several map stages: summed seconds, not a mean of shares
    two = late + [span("stage", 1000, 900, 7, stage_kind="shuffle_map"),
                  span("dispatch", 1000, 900, 7, program="p")]
    assert read(reg, STAGE, two) == pytest.approx(5.0)
    # a query with waits but no map stage gives no share
    assert read(reg, STAGE, [span("query", 0, 10), wait(1, 2, True)]) is None


@pytest.mark.parametrize("name, unit, layer, cells", [
    ("host_wait_s", "s", "entry", ALL_CELLS),
    ("blocking_waits_per_query", "count", "entry", ALL_CELLS),
    ("wait_ready_share", "%", "entry", ALL_CELLS),
    (STAGE, "%", "exchange and stages", ONE_CHIP),
])
def test_the_entry_is_found_by_name_with_its_cells(reg, name, unit, layer,
                                                   cells):
    (entry,) = [m for m in reg.manifest["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_span", "layer": layer,
                     "moves": "query_s.p50", "workloads": cells}
    assert layer in {m["layer"] for m in reg.manifest["per_layer"]
                     if m is not entry}
    assert [w["name"] for w in reg.manifest["workloads"]] == ALL_CELLS
    for cell in ALL_CELLS:
        listed = name in [m["name"] for m in reg.metrics(cell, "per_layer")]
        assert listed == (cell in cells)
    # appended after every entry the benchmark had
    names = [m["name"] for m in reg.manifest["per_layer"]]
    assert names.index(name) > names.index("slice_copy_share")
    assert [w["chips"] for w in reg.manifest["workloads"]
            if w["name"] not in cells] == ([] if cells == ALL_CELLS else [4])


def test_the_q06core_rehearsal_carries_the_four_on_its_traced_line():
    from test_y_decimal_cell import _rehearse

    line = json.loads(_rehearse("sf1_q06core_agg", 2147483693, 200_000)[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert metrics["host_wait_s"]["unit"] == "s"
    assert metrics["host_wait_s"]["value"] > 0
    assert metrics["blocking_waits_per_query"]["unit"] == "count"
    assert metrics["blocking_waits_per_query"]["value"] >= 1
    assert metrics["wait_ready_share"]["unit"] == "%"
    assert 0 <= metrics["wait_ready_share"]["value"] <= 100
    assert metrics[STAGE]["unit"] == "%"
    assert 0 < metrics[STAGE]["value"] < 100
    # what was there reads as it read: a wait is no child of the query span
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["query_self_share"]["value"] < 20
    assert metrics["shuffle_map_stage_s"]["value"] > 0
