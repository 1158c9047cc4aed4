"""Compile layer: rows of padding the shape canonicalization added per query
(compile_service.TELEMETRY canonicalization_waste_rows over the window, divided
by the window's queries): a batch repadded to its capacity rung, or a stage's
batch tuple padded to its count rung. Every program behind the pad pays for
these rows as for live ones. A counter the window never added to is absent
from the snapshot and reads 0; None where the window finished no query."""


def read(run):
    if not run["window"]:
        return None
    waste = run["telemetry"].get("canonicalization_waste_rows", 0)
    return waste / len(run["window"])
