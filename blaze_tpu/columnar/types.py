"""Spark-SQL-compatible type algebra with TPU device mappings.

Ref: the Arrow type algebra of the plan contract (blaze.proto:852-888) and
scalar type conversion (NativeConverters.scala convertScalarType/convertDataType).
We keep the same logical types but record how each lands on device:

  logical type          device representation
  --------------------  -----------------------------------------
  boolean               bool_ (cap,)
  int8/16/32/64         intN (cap,)
  float32/64            floatN (cap,)
  date32                int32 (cap,)   days since epoch
  timestamp[us]         int64 (cap,)   micros since epoch
  decimal(p<=18, s)     int64 (cap,)   unscaled value (Spark compact repr)
  decimal(p>18, s)      2 x int64 (cap,) hi/lo limb planes of the unscaled value
  string / binary       uint8 (cap, W) fixed-width bytes + int32 lengths
  null                  int8 zeros (all-invalid validity)

Decimals with p>18 (Spark uses int128) are device-native too: two int64 limb
planes, struct<hi, lo> (wide_decimal_storage below, columnar/int128.py), with
the expression and aggregation kernels of exprs/wide_decimal.py; the convert
strategy falls back only for the wide usages those kernels do not cover
(spark/converters._wide_usage_ok). `DataType.wide_decimal` tells the two apart.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np


class TypeKind(enum.Enum):
    NULL = 0
    BOOLEAN = 1
    INT8 = 2
    INT16 = 3
    INT32 = 4
    INT64 = 5
    FLOAT32 = 6
    FLOAT64 = 7
    STRING = 8
    BINARY = 9
    DATE = 10        # days since epoch, int32
    TIMESTAMP = 11   # microseconds since epoch, int64
    DECIMAL = 12     # unscaled int64 (p<=18)
    # nested types are carried through the plan but execute on host fallback
    LIST = 13
    MAP = 14
    STRUCT = 15


@dataclasses.dataclass(frozen=True)
class DataType:
    kind: TypeKind
    precision: int = 0          # decimal only
    scale: int = 0              # decimal only
    element: Optional["DataType"] = None  # list element / map value
    key: Optional["DataType"] = None      # map key
    fields: Tuple["Field", ...] = ()      # struct fields

    # ---- classification ----
    @property
    def is_string_like(self) -> bool:
        return self.kind in (TypeKind.STRING, TypeKind.BINARY)

    @property
    def is_numeric(self) -> bool:
        return self.kind in (
            TypeKind.INT8, TypeKind.INT16, TypeKind.INT32, TypeKind.INT64,
            TypeKind.FLOAT32, TypeKind.FLOAT64, TypeKind.DECIMAL,
        )

    @property
    def is_integral(self) -> bool:
        return self.kind in (TypeKind.INT8, TypeKind.INT16, TypeKind.INT32, TypeKind.INT64)

    @property
    def is_floating(self) -> bool:
        return self.kind in (TypeKind.FLOAT32, TypeKind.FLOAT64)

    @property
    def is_nested(self) -> bool:
        return self.kind in (TypeKind.LIST, TypeKind.MAP, TypeKind.STRUCT)

    @property
    def is_decimal(self) -> bool:
        return self.kind == TypeKind.DECIMAL

    @property
    def wide_decimal(self) -> bool:
        return self.kind == TypeKind.DECIMAL and self.precision > 18

    # ---- device mapping ----
    def jnp_dtype(self):
        m = {
            TypeKind.NULL: jnp.int8,
            TypeKind.BOOLEAN: jnp.bool_,
            TypeKind.INT8: jnp.int8,
            TypeKind.INT16: jnp.int16,
            TypeKind.INT32: jnp.int32,
            TypeKind.INT64: jnp.int64,
            TypeKind.FLOAT32: jnp.float32,
            TypeKind.FLOAT64: jnp.float64,
            TypeKind.DATE: jnp.int32,
            TypeKind.TIMESTAMP: jnp.int64,
            TypeKind.DECIMAL: jnp.int64,
        }
        if self.kind not in m:
            raise TypeError(f"type {self} has no dense device dtype")
        return m[self.kind]

    def np_dtype(self):
        return np.dtype(self.jnp_dtype().__name__ if self.kind != TypeKind.BOOLEAN else "bool")

    def byte_width(self) -> int:
        return self.np_dtype().itemsize

    def __repr__(self) -> str:
        if self.kind == TypeKind.DECIMAL:
            return f"decimal({self.precision},{self.scale})"
        return self.kind.name.lower()


@dataclasses.dataclass(frozen=True)
class Field:
    name: str
    dtype: DataType
    nullable: bool = True


@dataclasses.dataclass(frozen=True)
class Schema:
    fields: Tuple[Field, ...]

    def __init__(self, fields):
        object.__setattr__(self, "fields", tuple(fields))

    def names(self) -> List[str]:
        return [f.name for f in self.fields]

    def index_of(self, name: str) -> int:
        for i, f in enumerate(self.fields):
            if f.name == name:
                return i
        raise KeyError(name)

    def field(self, name: str) -> Field:
        return self.fields[self.index_of(name)]

    def __len__(self) -> int:
        return len(self.fields)

    def __iter__(self):
        return iter(self.fields)


NULL = DataType(TypeKind.NULL)
BOOLEAN = DataType(TypeKind.BOOLEAN)
INT8 = DataType(TypeKind.INT8)
INT16 = DataType(TypeKind.INT16)
INT32 = DataType(TypeKind.INT32)
INT64 = DataType(TypeKind.INT64)
FLOAT32 = DataType(TypeKind.FLOAT32)
FLOAT64 = DataType(TypeKind.FLOAT64)
STRING = DataType(TypeKind.STRING)
BINARY = DataType(TypeKind.BINARY)
DATE = DataType(TypeKind.DATE)
TIMESTAMP = DataType(TypeKind.TIMESTAMP)


def decimal(precision: int, scale: int) -> DataType:
    return DataType(TypeKind.DECIMAL, precision=precision, scale=scale)


def list_of(element: DataType) -> DataType:
    return DataType(TypeKind.LIST, element=element)


def map_of(key: DataType, value: DataType) -> DataType:
    return DataType(TypeKind.MAP, key=key, element=value)


def struct_of(fields) -> DataType:
    return DataType(TypeKind.STRUCT, fields=tuple(fields))


def wide_decimal_storage(dtype: DataType) -> DataType:
    """Physical storage of a decimal(p>18) column: struct<hi:int64,
    lo:int64> limb planes, value = hi * 2^64 + u64(lo) (columnar/int128.py
    — the engine's Decimal128, ref: arrow-rs i128 unscaled storage)."""
    assert dtype.wide_decimal
    return struct_of([Field("hi", INT64, nullable=False),
                      Field("lo", INT64, nullable=False)])


def storage_element(dtype: DataType) -> DataType:
    """Element dtype of the flat storage under a LIST or MAP column.

    A MAP column is stored as list<struct<key, value>> (Arrow's map layout),
    so its storage element is the entry struct, not the value type."""
    if dtype.kind == TypeKind.MAP:
        return struct_of([Field("key", dtype.key, nullable=False),
                          Field("value", dtype.element)])
    return dtype.element
