"""First-class packed storage for quantized embedding codes.

Every integer-table method in this repo stores its table as low-bit signed
codes plus per-row scales.  Historically the codes lived in an int8 array —
one full byte per code — so bits=4 and bits=2 saved *nothing* in resident or
moved bytes.  :class:`CodeStore` makes the container explicit:

    bits in {2, 4}   ->  packed uint8, ``8 // bits`` codes per byte
    bits in {5..8}   ->  one int8 byte per code (unchanged layout)

Packed layout (low-bits-first, matching the original ``quant.pack4``): logical
code ``j`` of a row lives in byte ``j // cpb`` at bit offset
``(j % cpb) * bits`` where ``cpb = 8 // bits``.  Rows whose logical width is
not a multiple of ``cpb`` are zero-padded to the next byte boundary; the pad
codes are never observable through :func:`unpack_codes` (it slices back to the
logical width).

The class is a registered pytree (one array child, static ``bits``/shape/
``packed`` aux), so it flows through ``jax.jit``, ``jax.eval_shape``, the
checkpoint manager's leaf-per-file layout, and ``jax.tree`` size accounting
without special cases.  The facade (``shape``/``dtype``/``size``/indexing)
reports the *logical* int8 view so shape-level consumers keep working, while
mutation goes through the explicit ``take`` / ``set_rows`` / ``where_rows``
API — there is deliberately no ``.at`` or ``.astype`` on a CodeStore, so a
call site that tries to mutate raw bytes fails loudly instead of silently
corrupting the packed container.

Bitwise-parity contract: ``pack_codes`` / ``unpack_codes`` are exact inverses
on the valid signed code range for their bit width, and every consumer does
its arithmetic on the *unpacked* values in the same operation order as the
unpacked path.  Packed-on therefore equals packed-off bit for bit — the
parity tests in tests/test_codestore.py hold every method to that bar.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

_PACKABLE_BITS = (2, 4)

__all__ = [
    "CodeStore",
    "is_packable",
    "codes_per_byte",
    "packed_width",
    "pack_codes",
    "unpack_codes",
]


def is_packable(bits: int) -> bool:
    """True when ``bits`` codes can share bytes (exact byte divisors only)."""
    return bits in _PACKABLE_BITS


def codes_per_byte(bits: int) -> int:
    if not is_packable(bits):
        raise ValueError(f"bits={bits} is not packable (need one of {_PACKABLE_BITS})")
    return 8 // bits


def packed_width(d: int, bits: int) -> int:
    """Bytes per row when packing ``d`` logical codes at ``bits`` bits."""
    cpb = codes_per_byte(bits)
    return -(-d // cpb)


def pack_codes(codes: jax.Array, bits: int) -> jax.Array:
    """Pack signed ``bits``-bit codes into uint8, ``8 // bits`` per byte.

    Operates over the last axis; any leading shape is preserved.  Odd lengths
    are zero-padded up to the next byte boundary.  Low-bits-first layout:
    logical code ``j`` lands in byte ``j // cpb`` at shift ``(j % cpb) * bits``
    (for bits=4 this is byte-for-byte the historical ``quant.pack4`` layout).
    """
    cpb = codes_per_byte(bits)
    mask = (1 << bits) - 1
    d = codes.shape[-1]
    w = packed_width(d, bits)
    u = codes.astype(jnp.int32) & mask
    pad = w * cpb - d
    if pad:
        u = jnp.pad(u, [(0, 0)] * (u.ndim - 1) + [(0, pad)])
    u = u.reshape(u.shape[:-1] + (w, cpb))
    shifts = (jnp.arange(cpb, dtype=jnp.int32) * bits)[(None,) * (u.ndim - 1)]
    return jnp.sum(u << shifts, axis=-1).astype(jnp.uint8)


def unpack_codes(packed: jax.Array, bits: int, d: int) -> jax.Array:
    """Inverse of :func:`pack_codes`: uint8 container back to int8 codes.

    ``d`` is the logical last-axis length (byte-boundary zero-pad is sliced
    off).  Values are sign-extended from ``bits`` bits, so the roundtrip is
    exact over the full signed code range ``[-2^(bits-1), 2^(bits-1))``.
    """
    cpb = codes_per_byte(bits)
    mask = (1 << bits) - 1
    shifts = jnp.arange(cpb, dtype=jnp.int32) * bits
    vals = (packed.astype(jnp.int32)[..., None] >> shifts) & mask
    flat = vals.reshape(vals.shape[:-2] + (vals.shape[-2] * cpb,))
    flat = flat[..., :d]
    half = 1 << (bits - 1)
    return jnp.where(flat >= half, flat - (1 << bits), flat).astype(jnp.int8)


@dataclasses.dataclass(frozen=True)
class CodeStore:
    """A table of ``n x d`` signed codes in an explicit byte container.

    ``data`` is ``uint8 [n, packed_width(d, bits)]`` when ``packed`` else the
    classic ``int8 [n, d]``.  ``bits``/``n``/``d``/``packed`` are static pytree
    aux, so two stores with different layouts never unify under ``jit``.
    """

    data: jax.Array
    bits: int
    n: int
    d: int
    packed: bool

    # ------------------------------------------------------------ build

    @classmethod
    def from_codes(cls, codes: jax.Array, bits: int,
                   packed: bool | None = None) -> "CodeStore":
        """Wrap raw int8 codes ``[n, d]``; packs when the width allows it.

        ``packed=None`` means "pack if possible"; asking for ``packed=True``
        at a non-packable width silently stores one byte per code (there is
        no denser layout for bits in {3, 5..8}).
        """
        n, d = codes.shape
        do_pack = is_packable(bits) if packed is None else (
            bool(packed) and is_packable(bits)
        )
        data = pack_codes(codes, bits) if do_pack else codes
        return cls(data=data, bits=int(bits), n=int(n), d=int(d),
                   packed=do_pack)

    def with_data(self, data: jax.Array) -> "CodeStore":
        return dataclasses.replace(self, data=data)

    # ------------------------------------------------------------ facade

    @property
    def shape(self) -> tuple[int, int]:
        """Logical (rows, codes-per-row) — not the byte container's shape."""
        return (self.n, self.d)

    @property
    def dtype(self):
        """Logical code dtype (the container dtype is ``self.data.dtype``)."""
        return jnp.int8

    @property
    def size(self) -> int:
        return self.n * self.d

    @property
    def ndim(self) -> int:
        return 2

    @property
    def resident_bytes(self) -> int:
        """Actual container bytes: ``ceil(d * bits / 8)`` per row if packed."""
        return int(
            math.prod(self.data.shape) * np.dtype(self.data.dtype).itemsize
        )

    # ------------------------------------------------------------ reads

    def unpack(self) -> jax.Array:
        """The full logical int8 ``[n, d]`` view (a copy when packed)."""
        if self.packed:
            return unpack_codes(self.data, self.bits, self.d)
        return self.data

    def take(self, ids: jax.Array) -> jax.Array:
        """Row gather -> int8 codes ``ids.shape + (d,)`` (out-of-range rows
        follow ``jnp.take``'s clamping, matching the raw-array path)."""
        rows = jnp.take(self.data, ids, axis=0)
        if self.packed:
            return unpack_codes(rows, self.bits, self.d)
        return rows

    def min(self):
        return self.unpack().min()

    def max(self):
        return self.unpack().max()

    def __getitem__(self, idx):
        return self.unpack()[idx]

    def __array__(self, dtype=None):
        arr = np.asarray(jax.device_get(self.unpack()))
        return arr.astype(dtype) if dtype is not None else arr

    def __jax_array__(self):
        # Escape hatch: lets stray `jnp.take(store, ...)`-style reads see the
        # logical int8 view.  Writes have no such hatch on purpose.
        return self.unpack()

    # ------------------------------------------------------------ writes

    def set_rows(self, rows_idx: jax.Array, codes_rows: jax.Array, *,
                 mode: str = "drop",
                 indices_are_sorted: bool = False) -> "CodeStore":
        """Functional row scatter: int8 ``[k, d]`` rows -> new CodeStore.

        Packs the incoming rows first when the container is packed, so the
        scatter moves container bytes (what the aliased Pallas path does
        in-kernel).
        """
        if self.packed:
            rows = pack_codes(codes_rows, self.bits)
        else:
            rows = codes_rows.astype(self.data.dtype)
        return self.with_data(self.data.at[rows_idx].set(
            rows, mode=mode, indices_are_sorted=indices_are_sorted
        ))

    def where_rows(self, row_mask: jax.Array,
                   codes_new: "CodeStore | jax.Array") -> "CodeStore":
        """Row-wise select: where ``row_mask`` take ``codes_new`` else self.

        ``row_mask`` is ``[n]`` or ``[n, 1]``; ``codes_new`` is a CodeStore
        with the same layout or raw int8 ``[n, d]``.  Selection happens on
        container bytes — row-wise masks commute with packing exactly.
        """
        if isinstance(codes_new, CodeStore):
            if (codes_new.packed, codes_new.bits) != (self.packed, self.bits):
                raise ValueError(
                    f"layout mismatch in where_rows: "
                    f"{(codes_new.packed, codes_new.bits)} vs "
                    f"{(self.packed, self.bits)}"
                )
            new_data = codes_new.data
        elif self.packed:
            new_data = pack_codes(codes_new, self.bits)
        else:
            new_data = codes_new.astype(self.data.dtype)
        mask = row_mask if row_mask.ndim == 2 else row_mask[:, None]
        return self.with_data(jnp.where(mask, new_data, self.data))


# The either-type row-access helpers that used to live here (logical_codes /
# take_rows / set_rows / where_rows / resident_bytes_of) are now the
# :mod:`repro.storage.base` RowStore protocol surface — one dispatch boundary
# shared by every container (CodeStore, TieredCodes, raw arrays).


def _flatten_with_keys(s: CodeStore):
    return ((jax.tree_util.GetAttrKey("data"), s.data),), (
        s.bits, s.n, s.d, s.packed,
    )


def _flatten(s: CodeStore):
    return (s.data,), (s.bits, s.n, s.d, s.packed)


def _unflatten(aux, children) -> CodeStore:
    bits, n, d, packed = aux
    return CodeStore(data=children[0], bits=bits, n=n, d=d, packed=packed)


jax.tree_util.register_pytree_with_keys(
    CodeStore, _flatten_with_keys, _unflatten, _flatten
)
