"""`RowStore`: the one row-access protocol every code container implements.

Before this module, the row-access surface was scattered: `core/codestore.py`
carried "either-type" helpers that dispatched on `isinstance(x, CodeStore)`,
and `serving/table.py` carried its own isinstance chains for row reads.
Every new container type (the tiered hot-row cache, the host-memory cold
tier) would have grown every one of those chains.

Now there is exactly one boundary: a container either *is* a raw
``jax.Array``/numpy array (the historical int8 codes layout) or it implements
the :class:`RowStore` protocol — ``unpack`` / ``take`` / ``set_rows`` /
``where_rows`` / ``resident_bytes``.  The module-level functions below are
the only dispatch sites; call sites never type-switch again.

Implementations in-tree:

* :class:`repro.core.codestore.CodeStore` — the HBM-resident (possibly
  packed sub-byte) warm tier;
* :class:`repro.storage.tiered.TieredCodes` — a device-resident hot-row
  cache composed over any other RowStore backing;
* raw int8 arrays — hand-built tables in tests, float exports.

Bitwise contract: for containers holding the same logical codes, every
function here returns bitwise-identical values whichever implementation
backs it — the cache-parity tests in tests/test_storage.py hold each
implementation to that bar.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "RowStore",
    "CacheSlot",
    "is_row_store",
    "logical_codes",
    "take_rows",
    "set_rows",
    "where_rows",
    "resident_bytes_of",
]


@runtime_checkable
class RowStore(Protocol):
    """A table of ``n x d`` logical int8 codes behind a storage layout.

    ``shape`` reports the *logical* geometry; the container may hold packed
    bytes, tiers, or host memory underneath.  All five operations are
    functional (writes return a new container).
    """

    @property
    def shape(self) -> tuple[int, int]: ...

    def unpack(self) -> jax.Array: ...

    def take(self, ids: jax.Array) -> jax.Array: ...

    def set_rows(self, rows_idx: jax.Array, codes_rows: jax.Array, *,
                 mode: str = "drop",
                 indices_are_sorted: bool = False) -> "RowStore": ...

    def where_rows(self, row_mask: jax.Array,
                   codes_new: "RowStore | jax.Array") -> "RowStore": ...

    @property
    def resident_bytes(self) -> int: ...


def is_row_store(codes) -> bool:
    """True for protocol containers; False for raw jax/numpy code arrays.

    Duck-typed on ``where_rows`` (raw arrays have ``take`` but none of the
    functional write surface), so this module never imports the container
    classes — new RowStore implementations need no registration here.
    """
    return hasattr(codes, "where_rows")


def logical_codes(codes) -> jax.Array:
    """The unpacked int8 [n, d] view of any container."""
    return codes.unpack() if is_row_store(codes) else codes


def take_rows(codes, ids: jax.Array) -> jax.Array:
    """Row gather -> int8 codes ``ids.shape + (d,)``."""
    if is_row_store(codes):
        return codes.take(ids)
    return jnp.take(codes, ids, axis=0)


def set_rows(codes, rows_idx: jax.Array, codes_rows: jax.Array, *,
             mode: str = "drop", indices_are_sorted: bool = False):
    """Functional row scatter of int8 ``[k, d]`` rows -> new container.

    ``indices_are_sorted`` promises ``rows_idx`` non-decreasing.
    """
    if is_row_store(codes):
        return codes.set_rows(rows_idx, codes_rows, mode=mode,
                              indices_are_sorted=indices_are_sorted)
    return codes.at[rows_idx].set(codes_rows, mode=mode,
                                  indices_are_sorted=indices_are_sorted)


def where_rows(codes, row_mask: jax.Array, codes_new):
    """Row-wise select: where ``row_mask`` take ``codes_new`` else ``codes``."""
    if is_row_store(codes):
        return codes.where_rows(row_mask, codes_new)
    mask = row_mask if row_mask.ndim == 2 else row_mask[:, None]
    return jnp.where(mask, logical_codes(codes_new), codes)


def resident_bytes_of(codes) -> int:
    """Container-actual resident bytes of any representation."""
    if is_row_store(codes):
        return int(codes.resident_bytes)
    return int(math.prod(codes.shape) * np.dtype(codes.dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class CacheSlot:
    """One cacheable sub-table of a composed state (training or serving).

    The tiered cache operates per *slot* — a single-table method has one
    identity slot; qr methods have remainder/quotient slots; the mixed
    method has one slot per bit-width group.  ``get``/``put`` project the
    slot's table out of / back into the enclosing state; ``local_ids`` maps
    global feature ids to the slot's local row space (entries outside the
    slot map to -1 and are ignored by the cache policy).
    """

    name: str
    rows: int  # live local id space of the slot's table
    get: Callable[[Any], Any]
    put: Callable[[Any, Any], Any]
    local_ids: Callable[[np.ndarray], np.ndarray]
