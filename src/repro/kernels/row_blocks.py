"""Row access inside TPU-tiled blocks, for the row-gather kernel.

Mosaic refuses a ``(1, d)`` block, a ``(1, 1)`` block and a dynamic row
index into an int8 block: a block's last two dimensions must be multiples of
the dtype's tile (8 sublanes for f32, 32 for int8/uint8) or span the array.
So the row-gather kernel moves one *row group* per grid step -- ``GROUP``
table rows, the int8 sublane tile, which also covers the f32 tile -- picks
the wanted row with an iota mask and places it in its output block with
another.  The mask ops are exact: a select
keeps every bit, and a masked integer sum adds zeros to one value.

Packed sub-byte containers are spread to one code per lane with a 0/1
matrix on the MXU.  Each output is one byte value (at most 255), so the
products and sums are exact at any matmul precision.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: Table rows per block: the int8/uint8 sublane tile (a multiple of f32's 8).
GROUP = 32
#: Rows per block of the dense per-id operands and outputs (the f32 tile).
OUT_ROWS = 8


def _row_mask(shape, r):
    return jax.lax.broadcasted_iota(jnp.int32, shape, 0) == r


def select_row(block: jax.Array, r) -> jax.Array:
    """Row ``r`` of a 2-D block as ``(1, w)``, bit for bit.

    Float blocks go through their int32 bits, so -0.0 and NaN payloads
    survive the masked sum."""
    if jnp.issubdtype(block.dtype, jnp.floating):
        bits = jax.lax.bitcast_convert_type(block, jnp.int32)
        row = jnp.sum(jnp.where(_row_mask(bits.shape, r), bits, 0), axis=0,
                      keepdims=True)
        return jax.lax.bitcast_convert_type(row, block.dtype)
    block = block.astype(jnp.int32)
    return jnp.sum(jnp.where(_row_mask(block.shape, r), block, 0), axis=0,
                   keepdims=True)


def put_row(block: jax.Array, r, row: jax.Array) -> jax.Array:
    """``block`` with row ``r`` replaced by the ``(1, w)`` ``row``."""
    return jnp.where(_row_mask(block.shape, r), row, block)


def _spread_matrix(w: int, d: int, cpb: int) -> jax.Array:
    """0/1 ``(w, d)``: entry (b, c) is 1 where code ``c`` lives in byte b."""
    byte = jax.lax.broadcasted_iota(jnp.int32, (w, d), 0)
    code = jax.lax.broadcasted_iota(jnp.int32, (w, d), 1)
    return (code // cpb == byte).astype(jnp.float32)


def unpack_block(packed: jax.Array, bits: int, d: int) -> jax.Array:
    """uint8 ``(g, w)`` container block -> int32 ``(g, d)`` signed codes
    (the layout of :func:`repro.core.codestore.unpack_codes`)."""
    cpb = 8 // bits
    w = packed.shape[-1]
    spread = jnp.dot(
        packed.astype(jnp.int32).astype(jnp.float32),
        _spread_matrix(w, d, cpb),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)  # each lane holds its code's whole byte
    shift = (jax.lax.broadcasted_iota(jnp.int32, (1, d), 1) % cpb) * bits
    u = jax.lax.shift_right_logical(spread, shift) & ((1 << bits) - 1)
    half = 1 << (bits - 1)
    return jnp.where(u >= half, u - (1 << bits), u)

