"""Memory layer: peak_bytes_in_use after the window on the fullest device."""


def read(run):
    peak = run["memory_peak_bytes"]
    return None if peak is None else peak / 1e9
