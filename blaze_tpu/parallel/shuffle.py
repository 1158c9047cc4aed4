"""On-mesh shuffle: murmur3 partitioning + `lax.all_to_all` exchange.

The reference's shuffle repartitions rows by Spark-murmur3 and moves the
buckets between executors as zstd-IPC files over netty (SURVEY.md §3.3).
When the stage's partitions map onto one TPU slice, we instead do the whole
exchange in HBM over ICI: each device groups its rows by destination
partition into a fixed-quota staging buffer and a single `all_to_all`
delivers every bucket — the Spark-compatible partition function is shared
with the file-based path (exprs/hash.py: hash(seed=42) then pmod, ref
datafusion-ext-plans shuffle/mod.rs:94-119).

Everything here is shape-static and jit-safe inside `shard_map`; the only
lossy edge is quota overflow (more than `quota` rows bound for one partition
from one device), which is *reported*, not silently dropped on the floor —
callers fall back to the file-based path when overflow > 0.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from blaze_tpu.columnar.batch import (
    Column, ColumnBatch, StringData, nonzero_i32,
)
from blaze_tpu.exprs.hash import SPARK_SHUFFLE_SEED, hash_columns, pmod

Array = jax.Array


def partition_ids(batch: ColumnBatch, key_indices: Sequence[int],
                  num_partitions: int,
                  seed: int = SPARK_SHUFFLE_SEED) -> Array:
    """Destination partition per row; padding rows get sentinel P.

    Spark-compatible: murmur3(seed 42) over the key columns then pmod
    (shuffle/mod.rs:94-119). The sentinel makes padding sort after all real
    partitions so grouping logic can ignore it.
    """
    keys = [batch.columns[i] for i in key_indices]
    mask = batch.row_mask()
    if not keys:
        # round-robin-ish fallback: row index mod P (ref uses round robin for
        # RoundRobinPartitioning; exact start offset does not matter for
        # correctness of the exchange)
        pid = jnp.arange(batch.capacity, dtype=jnp.int32) % num_partitions
    else:
        h = hash_columns(keys, seed, row_mask=mask)
        pid = pmod(h, num_partitions)
    return jnp.where(mask, pid, jnp.int32(num_partitions))


def _stage_by_partition(batch: ColumnBatch, pid: Array, num_partitions: int,
                        quota: int) -> Tuple[ColumnBatch, Array, Array]:
    """Group rows into a (P*quota)-capacity staged batch, bucket-major.

    Returns (staged batch, per-partition counts (P,), overflow count scalar).
    Slot j of bucket p holds the j-th row destined to p; slots >= count_p are
    garbage (masked by the returned counts).
    """
    P = num_partitions
    cap = batch.capacity
    order = jnp.argsort(pid, stable=True)
    pid_sorted = pid[order]
    bounds = jnp.searchsorted(pid_sorted, jnp.arange(P + 1, dtype=pid.dtype))
    starts, ends = bounds[:-1], bounds[1:]
    counts = (ends - starts).astype(jnp.int32)
    overflow = jnp.sum(jnp.maximum(counts - quota, 0))
    j = jnp.arange(quota, dtype=jnp.int32)
    idx = starts[:, None].astype(jnp.int32) + j[None, :]      # (P, quota)
    idx = jnp.clip(idx, 0, cap - 1)
    gather = order[idx].reshape(-1)                            # (P*quota,)
    staged = batch.take(gather, jnp.asarray(0, jnp.int32))
    return staged, jnp.minimum(counts, quota), overflow


def staged_all_to_all(batch: ColumnBatch, pid: Array, axis_name: str,
                      num_partitions: int, quota: int,
                      ) -> Tuple[ColumnBatch, Array]:
    """Exchange rows to their destination partitions over a mesh axis.

    Must be called inside `shard_map` over `axis_name` with exactly
    `num_partitions` devices. Returns (received batch compacted to the
    front, overflow count) — received capacity is P*quota.
    """
    P = num_partitions
    staged, counts, overflow = _stage_by_partition(batch, pid, P, quota)

    def exchange(a: Array) -> Array:
        a = a.reshape(P, quota, *a.shape[1:])
        a = lax.all_to_all(a, axis_name, split_axis=0, concat_axis=0)
        return a.reshape(P * quota, *a.shape[2:])

    cols = []
    for c in staged.columns:
        if c.is_string:
            # covers DictData too: its lazy bytes/lengths expand in-jit,
            # since per-device dictionaries cannot ride all_to_all
            data = StringData(exchange(c.data.bytes), exchange(c.data.lengths))
        else:
            # row-aligned storages (dense arrays, wide-decimal limb-plane
            # structs) exchange per pytree leaf; LIST storage cannot ride
            # the mesh path (element storage isn't row-aligned) and is
            # screened out by run_mesh_shuffle_stage's shape checks
            data = jax.tree_util.tree_map(exchange, c.data)
        validity = exchange(c.validity) if c.validity is not None else None
        cols.append(Column(c.dtype, data, validity))

    # counts (P,) -> each device learns how many rows each peer sent it
    recv_counts = lax.all_to_all(counts.reshape(P, 1), axis_name,
                                 split_axis=0, concat_axis=0).reshape(P)
    slot = jnp.arange(quota, dtype=jnp.int32)
    recv_valid = (slot[None, :] < recv_counts[:, None]).reshape(-1)
    received = ColumnBatch(staged.schema, cols,
                           jnp.sum(recv_counts), P * quota)
    # compact live rows to the front (padding content is garbage otherwise)
    mask = recv_valid
    n = jnp.sum(mask, dtype=jnp.int32)
    out = received.take(nonzero_i32(mask, P * quota), n)
    total_overflow = lax.psum(overflow, axis_name)
    return out, total_overflow


def mesh_shuffle_batch(batch: ColumnBatch, key_indices: Sequence[int],
                       axis_name: str, num_partitions: int,
                       quota: Optional[int] = None,
                       ) -> Tuple[ColumnBatch, Array]:
    """Hash-repartition a per-device batch across the mesh axis.

    The single-call equivalent of the reference's ShuffleWriter+IpcReader
    pair for the on-slice case.
    """
    quota = quota or batch.capacity
    pid = partition_ids(batch, key_indices, num_partitions)
    return staged_all_to_all(batch, pid, axis_name, num_partitions, quota)


def mesh_shuffle_batch_grouped(batch: ColumnBatch,
                               key_indices: Sequence[int], axis_name: str,
                               num_partitions: int, parts_per_device: int,
                               quota: int,
                               ) -> Tuple[ColumnBatch, Array, Array]:
    """Partitions-per-device exchange: P = D * parts_per_device logical
    partitions over a D-device axis (the P > D case VERDICT r4 #7 asks
    for). Device d OWNS partitions [d*k, (d+1)*k); rows route to their
    owner with ONE all_to_all over D owner groups (quota rows per
    destination device per source device), then each device groups its
    received rows by logical partition locally.

    Returns (received batch sorted by logical pid with live rows first,
    per-owned-partition row counts (k,), total overflow). Must run inside
    shard_map over `axis_name`.
    """
    P, k = num_partitions, parts_per_device
    pid = partition_ids(batch, key_indices, P)
    dsize = lax.axis_size(axis_name)
    # owner device of each row; padding rows carry the sentinel group D
    owner = jnp.where(pid >= P, jnp.int32(dsize), pid // k)
    received, overflow = staged_all_to_all(batch, owner, axis_name, dsize,
                                           quota)
    # local sub-grouping: sort received rows by logical pid (live first)
    rpid = partition_ids(received, key_indices, P)
    live = received.row_mask()
    skey = jnp.where(live, rpid, jnp.int32(P)).astype(jnp.uint32)
    from blaze_tpu.ops.join import sort_batch_by_keys

    grouped = sort_batch_by_keys(received, [skey])
    me = lax.axis_index(axis_name)
    base = (me.astype(jnp.int32)) * k
    spid = jnp.sort(skey)
    bounds = jnp.searchsorted(
        spid, (base + jnp.arange(k + 1, dtype=jnp.int32)).astype(jnp.uint32))
    counts = (bounds[1:] - bounds[:-1]).astype(jnp.int32)
    return grouped, counts, overflow
