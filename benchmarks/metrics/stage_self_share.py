"""Exchange + stages layer: the host's own Python in a map stage. Over a
query's `stage` spans of kind shuffle_map: 100 x sum(duration - covered) /
sum(duration), median per query, where covered is the union of the query's
activity spans (`wait`, `dispatch`, `exchange`, `h2d`, `d2h`, `scan_decode`,
...: every kind but the containers `stage` and `task_attempt`) that carry the
stage's `stage_id`, lie on the stage span's thread and are clipped to its
interval. Nested and overlapping spans count once. What is left is the driver
in no span: building keys and plans, eager jnp calls, generators, the pool's
hand-offs. With host_wait_s, dispatches_per_query and exchange_s it closes the
account of a map stage. One-chip cells only: on four chips the tasks run on
pool threads and the driver's uncovered time is waiting for them. Program
spans, host clock. None where no query recorded a `wait` span (a program from
before them: its pulls would read as self)."""
import statistics

CONTAINERS = ("stage", "task_attempt")


def _union(intervals):
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total, end = total + (b - a), b
        elif b > end:
            total, end = total + (b - end), b
    return total


def read(run):
    shares = []
    for q in run["window"] + run["profiled"]:
        spans = q["spans"] or []
        if not any(s["kind"] == "wait" for s in spans):
            continue
        whole = own = 0
        for st in spans:
            if (st["kind"] != "stage" or not st.get("dur") or
                    st.get("attrs", {}).get("stage_kind") != "shuffle_map"):
                continue
            t0, t1 = st["ts"], st["ts"] + st["dur"]
            inside = [(max(s["ts"], t0), min(s["ts"] + s["dur"], t1))
                      for s in spans if s["kind"] not in CONTAINERS
                      and s.get("stage_id") == st.get("stage_id")
                      and s.get("thread") == st.get("thread")]
            whole += st["dur"]
            own += st["dur"] - _union([c for c in inside if c[1] > c[0]])
        if whole:
            shares.append(100.0 * own / whole)
    return statistics.median(shares) if shares else None
