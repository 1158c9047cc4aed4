"""The comparison that decides `correct`: the system's result against the
plain reference, row for row (copied in spirit from spark/validator._compare,
kept here so no later PR can move it)."""

from __future__ import annotations

from typing import Optional

import numpy as np
import pandas as pd


def to_frame(batch) -> pd.DataFrame:
    """Device batch -> host frame; pulling every column forces the device
    to finish, so this belongs inside the timed interval."""
    return pd.DataFrame({k: v if isinstance(v, np.ndarray) else list(v)
                         for k, v in batch.to_numpy().items()})


def _floats(a: np.ndarray) -> np.ndarray:
    if a.dtype.kind == "O":
        return np.where(pd.isna(a), np.nan, a).astype(np.float64)
    return a.astype(np.float64)


def _is_text(a: np.ndarray) -> bool:
    if a.dtype.kind in "US":
        return True
    return a.dtype.kind == "O" and any(
        isinstance(x, (str, bytes)) for x in a[:64] if x is not None)


def diff(got: pd.DataFrame, want: pd.DataFrame, rtol: float,
         order_keys=None) -> Optional[str]:
    """None when equal; else the first difference. `order_keys`: the query
    has no ORDER BY, so both sides are sorted by these columns first."""
    if len(got) != len(want):
        return f"row count {len(got)} != {len(want)}"
    missing = [c for c in want.columns if c not in got.columns]
    if missing:
        return f"missing columns {missing}"
    if order_keys:
        got = got.sort_values(order_keys, kind="stable")
        want = want.sort_values(order_keys, kind="stable")
    for c in want.columns:
        g, w = got[c].to_numpy(), want[c].to_numpy()
        if _is_text(w):
            gs = np.array([x.decode() if isinstance(x, bytes) else x
                           for x in g], object)
            bad = gs != w.astype(object)
        elif "f" in (g.dtype.kind, w.dtype.kind) or \
                "O" in (g.dtype.kind, w.dtype.kind):
            bad = ~np.isclose(_floats(g), _floats(w), rtol=rtol, atol=0.0,
                              equal_nan=True)
        else:
            bad = g.astype(np.int64) != w.astype(np.int64)
        if bad.any():
            i = int(np.argmax(bad))
            return (f"column {c}: {int(bad.sum())} of {len(bad)} rows "
                    f"differ, first at row {i}: got {g[i]!r} want {w[i]!r}")
    return None
