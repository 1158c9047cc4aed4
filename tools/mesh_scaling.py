"""Mesh-exchange scaling measurement (VERDICT r4 #7 artifact).

Times the grouped all_to_all exchange (parallel/shuffle.py) at a given
virtual-CPU-mesh size and prints one JSON line. Driven per device count
by tools/run_mesh_scaling.sh, which aggregates MESH_SCALING_r{N}.json —
the multi-chip perf story the correctness-only dryrun lacked.

    XLA_FLAGS=--xla_force_host_platform_device_count=D \
    JAX_PLATFORMS=cpu python tools/mesh_scaling.py [P]

Measures steady-state per-exchange time (jit warm, scan-differenced so
dispatch overhead is excluded) for a per-device batch of 2^16 rows x
(i64 key + f64 value), P logical partitions over the D devices.
"""

import json
import sys
import time

sys.path.insert(0, ".")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as PS  # noqa: E402

from blaze_tpu.columnar import types as T  # noqa: E402
from blaze_tpu.columnar.batch import ColumnBatch  # noqa: E402
from blaze_tpu.parallel.shuffle import (  # noqa: E402
    mesh_shuffle_batch_grouped,
)

ROWS = 1 << 16
SCHEMA = T.Schema([T.Field("k", T.INT64), T.Field("v", T.FLOAT64)])


def main() -> None:
    P = int(sys.argv[1]) if len(sys.argv) > 1 else 16
    D = len(jax.devices())
    kpd = -(-P // D)
    rng = np.random.default_rng(3)
    n = D * ROWS
    batch = ColumnBatch.from_numpy(
        {"k": rng.integers(0, 1 << 20, n).astype(np.int64),
         "v": rng.random(n)}, SCHEMA, capacity=n)
    num_rows = jnp.full((D,), ROWS, jnp.int32)
    mesh = Mesh(np.array(jax.devices()), ("p",))

    def step(local_cols, local_num_rows):
        b = ColumnBatch(SCHEMA, local_cols, local_num_rows[0], ROWS)
        out, counts, overflow = mesh_shuffle_batch_grouped(
            b, [0], "p", P, kpd, quota=ROWS * kpd)
        return out.columns, counts[None], overflow[None]

    inner = jax.shard_map(step, mesh=mesh, in_specs=(PS("p"), PS("p")),
                          out_specs=(PS("p"), PS("p"), PS("p")))

    def scan_n(reps):
        def run(cols, num_rows):
            def body(c, _):
                out_cols, counts, ovf = inner(
                    jax.tree_util.tree_map(
                        lambda a: a + c.astype(a.dtype)
                        if jnp.issubdtype(a.dtype, jnp.integer) else a,
                        cols),
                    num_rows)
                s = sum(jnp.sum(x).astype(jnp.int64)
                        for x in jax.tree_util.tree_leaves(counts))
                return c + (s % 7).astype(jnp.int32), None
            c, _ = jax.lax.scan(body, jnp.int32(0), None, length=reps)
            return c
        return jax.jit(run)

    f1, f2 = scan_n(3), scan_n(13)
    args = (jax.tree_util.tree_map(lambda c: c, batch.columns), num_rows)
    np.asarray(f1(*args))
    np.asarray(f2(*args))
    t = time.time(); np.asarray(f1(*args)); d1 = time.time() - t
    t = time.time(); np.asarray(f2(*args)); d2 = time.time() - t
    per = (d2 - d1) / 10
    total_bytes = D * ROWS * 16  # i64 + f64, validity-free
    print(json.dumps({
        "devices": D, "partitions": P, "rows_per_device": ROWS,
        "exchange_ms": round(per * 1e3, 2),
        "bytes_per_s": round(total_bytes / per, 0),
    }))


if __name__ == "__main__":
    main()
