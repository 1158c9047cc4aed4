"""Which chip owns which task: the one rule of a multi-chip host.

A mesh exchange of P partitions over D visible devices gives device d the
contiguous block [d*k, (d+1)*k), k = ceil(P/D) (`layout`); partition p's
rows stay on `owner(p, P)` after the exchange, and task p of the stage
that consumes them runs there (`on_device`: jax's thread-local default
device, so uploads and operator-made arrays land on that chip and the
task's single-device programs run on it). parallel/stage_exchange.py
(where a partition is cut out and where the next exchange finds its
input), spark/local_runner.py (which tasks are placed) and
runtime/supervisor.py (the scope round an attempt, the `device` attr of
its span) all read this module and nothing else decides a placement.

One device: `owner` is None and `on_device(None)` does nothing, so the
one-chip path runs exactly as it did before placement existed.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Tuple

import jax

_tls = threading.local()


def layout(num_partitions: int, ndev: int) -> Tuple[int, int]:
    """(devices used, partitions per device) for a `num_partitions`-wide
    exchange over `ndev` visible devices: devices left with no partition
    are dropped."""
    use_d = max(1, min(ndev, num_partitions))
    kpd = -(-num_partitions // use_d)
    return -(-num_partitions // kpd), kpd


def owner(partition: int, num_partitions: int):
    """The device partition `partition` of a `num_partitions`-wide mesh
    exchange lives on, or None where the exchange has one device."""
    devices = jax.devices()
    use_d, kpd = layout(num_partitions, len(devices))
    return devices[partition // kpd] if use_d > 1 else None


@contextlib.contextmanager
def on_device(device):
    """Run the block with `device` as this thread's default device and as
    `current()`. None: no scope at all."""
    if device is None:
        yield
        return
    prev = getattr(_tls, "device", None)
    _tls.device = device
    try:
        with jax.default_device(device):
            yield
    finally:
        _tls.device = prev


def current():
    """The device this thread's task was placed on, or None."""
    return getattr(_tls, "device", None)


def here():
    """The device this thread's programs go to: the task's chip, else the
    process's default device."""
    return (current() or jax.config.jax_default_device
            or jax.devices()[0])


def device_of(tree):
    """The device the first array of `tree` lies on (a batch's columns lie
    on one device), or None for a tree without arrays or one whose
    arrays are sharded."""
    for x in jax.tree_util.tree_leaves(tree):
        if isinstance(x, jax.Array):
            devs = x.devices()
            return next(iter(devs)) if len(devs) == 1 else None
    return None


def put(tree, device):
    """`tree` with its arrays on `device`: as it is where they lie there
    already, else a copy, chip to chip."""
    return tree if device_of(tree) == device else jax.device_put(tree, device)
