"""Device layer: 1 - busy/wall over three consecutive profiled queries, busy =
union of the device's XLA-op intervals; mean over the cell's devices, in
percent (each device's own value is printed on an earlier line)."""


def read(run):
    reduction = run["reduction"]
    if not reduction:
        return None
    return 100.0 * (1.0 - reduction["busy_s"] / reduction["window_s"])
