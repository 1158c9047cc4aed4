"""Canonical plan fingerprints: stable hashes of operator-subtree SHAPE.

The query-history store (runtime/history.py) aggregates observed
statistics — row counts, stage wall times, copy traffic, groupby
cardinality — across runs of the *same plan*. "Same plan" must survive
the things that legitimately change between runs of one logical query:
literal values in predicates (`price > 5` vs `price > 7`), scan file
paths/sizes (a re-generated table directory), and task-scoped artifacts
(shuffle data/index paths the runner rewrites per task). The fingerprint
is a sha256 over a canonical token walk of the plan proto that masks
exactly those:

  literals     a ScalarValue contributes only its DataType (the dtype
               changes the compiled program; the value does not)
  file facts   PartitionedFile path/size/range/mtime and the shuffle
               writer's data_file/index_file are dropped — the scan
               *schema* and projection stay in
  namespaces   `*resource_id` fields hash only their local part — the
               per-query "qNNN-N/" prefix the multi-tenant service
               prepends (spark/stages.py) varies every run; the local
               "shuffle:0" / "broadcast:1" form is real plan shape
  everything   else — node kinds, expression operators, column names,
               function/agg enums, join types, partition counts — is
               hashed structurally, so any shape change re-keys

Two entry points:

  fingerprint_plan(msg)      proto-side (pb.PlanNode, or any plan proto
                             message) — computed per stage by the local
                             runner and stamped on stage spans / ledger
                             lines / history records
  fingerprint_operator(op)   decoded-Operator-side (ops/base.Operator) —
                             derived from plan_key() (the jit-cache's
                             literal-free structure key); used by the
                             whole-stage compiler and the per-op row taps

The two walk different representations so they hash into different (but
individually stable) keyspaces; the StatisticsFeed treats fingerprints
as opaque keys, so both aggregate correctly.
"""

from __future__ import annotations

import hashlib
from typing import List

# run-varying facts that must not re-key a plan: task-scoped shuffle
# artifact paths, and scan-file identity/stat fields (a re-generated
# table keeps its schema but not its paths or mtimes)
_MASKED_FIELDS = frozenset({
    "data_file", "index_file",           # ShuffleWriterNode (task-scoped)
    "path", "size", "range_start",       # PartitionedFile / ParquetSink
    "range_end", "last_modified_ns",
})

# resource ids carry a per-query namespace under the multi-tenant
# service ("q123-4/shuffle:0" — spark/stages.py); only the local part
# is plan shape, the qid prefix varies every run
_RESOURCE_ID_SUFFIX = "resource_id"

_HEX_CHARS = 16  # 64 bits of sha256 — plenty for a per-project store


def _digest(tokens: List[str]) -> str:
    return hashlib.sha256("\x00".join(tokens).encode()).hexdigest()[
        :_HEX_CHARS]


def _walk(msg, out: List[str]) -> None:
    desc = getattr(msg, "DESCRIPTOR", None)
    if desc is None:  # plain scalar (shouldn't happen at the top level)
        out.append(repr(msg))
        return
    out.append("(" + desc.name)
    if desc.name == "ScalarValue":
        # literal mask: type only — `x > 5` and `x > 7` fingerprint the
        # same; `x > 5` and `x > 'a'` do not
        out.append("lit")
        _walk(msg.dtype, out)
        out.append(")")
        return
    for fd, val in msg.ListFields():
        if fd.name in _MASKED_FIELDS:
            continue
        out.append(fd.name)
        if fd.type == fd.TYPE_MESSAGE:
            if fd.is_repeated:
                for v in val:
                    _walk(v, out)
            else:
                _walk(val, out)
        elif fd.is_repeated:
            out.extend(str(v) for v in val)
        elif fd.name.endswith(_RESOURCE_ID_SUFFIX):
            out.append(str(val).rsplit("/", 1)[-1])
        else:
            out.append(str(val))
    out.append(")")


def fingerprint_plan(msg) -> str:
    """Stable hex fingerprint of a plan proto message's shape (literals,
    file paths and task-scoped artifacts masked — see module doc)."""
    tokens: List[str] = []
    _walk(msg, tokens)
    return _digest(tokens)


def fingerprint_operator(op) -> str:
    """Stable hex fingerprint of a decoded Operator tree, derived from
    plan_key() — the jit cache's literal-free structural key. Hashed
    into the same opaque-key space history records index by (distinct
    from the proto-side keyspace, which carries more shape detail)."""
    return _digest(["opkey", repr(op.plan_key())])


def fingerprint_query(stage_fps: List[str]) -> str:
    """Query-level fingerprint: the ordered stage fingerprints hashed
    together (two runs match iff every stage shape matched, in order)."""
    return _digest(["query"] + list(stage_fps))
