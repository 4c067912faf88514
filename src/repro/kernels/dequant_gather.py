"""Pallas TPU kernel: fused int8-row gather + per-row de-quantize.

This is the LPT forward (paper §2.3): only the rows a batch touches leave the
integer table.  The ids are *scalar-prefetched* into SMEM so they drive the
BlockSpec index maps: each grid step DMAs the row group that holds one id
(``row_blocks.GROUP`` rows of int8 codes and their step sizes) HBM->VMEM,
picks the id's row with an iota mask, multiplies by its step size, and
merges the f32 row into an ``OUT_ROWS``-row output block that stays in VMEM
for ``OUT_ROWS`` consecutive ids.  The fp table never materializes in HBM.

Roofline: the op is pure memory traffic; int8 codes move 1 byte/elem where
an fp32 gather moves 4, though each step moves its whole row group.

Grid: ``(d // d_block, b)`` with the id axis innermost, so an output block is
complete before the next one starts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.row_blocks import (
    GROUP, OUT_ROWS, put_row, select_row, unpack_block,
)


def _kernel(ids_ref, codes_ref, step_ref, out_ref):
    # codes_ref: (GROUP, d_block) int8 group holding the id's row.
    # step_ref:  (GROUP, 1) f32 step sizes of that group.
    i = pl.program_id(1)
    r = ids_ref[i] % GROUP
    codes = select_row(codes_ref[...], r).astype(jnp.float32)
    row = codes * select_row(step_ref[...], r)
    out_ref[...] = put_row(out_ref[...], i % OUT_ROWS, row)


def _kernel_packed(ids_ref, codes_ref, step_ref, out_ref, *, bits, d):
    # codes_ref: (GROUP, w) packed uint8 group -- the HBM->VMEM DMA moved
    # bits/8 bytes per code; the sub-byte codes only exist unpacked in VMEM.
    i = pl.program_id(0)
    r = ids_ref[i] % GROUP
    codes = unpack_block(codes_ref[...], bits, d)
    row = select_row(codes, r).astype(jnp.float32) * select_row(
        step_ref[...], r
    )
    out_ref[...] = put_row(out_ref[...], i % OUT_ROWS, row)


def _padded(b: int) -> int:
    return -(-b // OUT_ROWS) * OUT_ROWS


def dequant_gather(
    codes: jax.Array,  # int8 [n, d]
    step: jax.Array,  # f32  [n]
    ids: jax.Array,  # int32 [b]
    *,
    d_block: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns f32 [b, d] de-quantized rows."""
    n, d = codes.shape
    (b,) = ids.shape
    d_block = min(d_block, d)
    if d % d_block != 0:
        raise ValueError(f"d={d} must be a multiple of d_block={d_block}")
    step2d = step.reshape(n, 1)

    grid = (d // d_block, b)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # The row group holding ids[i]; the kernel masks out its row.
            pl.BlockSpec(
                (GROUP, d_block), lambda j, i, ids_ref: (ids_ref[i] // GROUP, j)
            ),
            pl.BlockSpec(
                (GROUP, 1), lambda j, i, ids_ref: (ids_ref[i] // GROUP, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (OUT_ROWS, d_block), lambda j, i, ids_ref: (i // OUT_ROWS, j)
        ),
    )
    fn = pl.pallas_call(
        _kernel,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((_padded(b), d), jnp.float32),
        interpret=interpret,
    )
    return fn(ids.astype(jnp.int32), codes, step2d)[:b]


def dequant_gather_packed(
    packed: jax.Array,  # uint8 [n, w] packed container (w = ceil(d*bits/8))
    step: jax.Array,  # f32  [n]
    ids: jax.Array,  # int32 [b]
    *,
    bits: int,
    d: int,
    interpret: bool = False,
) -> jax.Array:
    """Packed-container gather: moves w bytes/row from HBM, unpacks in VMEM.

    Returns f32 [b, d] de-quantized rows, bitwise equal to
    ``dequant_gather(unpack_codes(packed), ...)`` — the unpack is exact and
    the de-quantize runs in the same operation order.  Rows stay whole (one
    grid step per id): sub-byte column tiling would split mid-byte.
    """
    n, w = packed.shape
    (b,) = ids.shape
    step2d = step.reshape(n, 1)

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((GROUP, w), lambda i, ids_ref: (ids_ref[i] // GROUP, 0)),
            pl.BlockSpec((GROUP, 1), lambda i, ids_ref: (ids_ref[i] // GROUP, 0)),
        ],
        out_specs=pl.BlockSpec((OUT_ROWS, d), lambda i, ids_ref: (i // OUT_ROWS, 0)),
    )
    fn = pl.pallas_call(
        functools.partial(_kernel_packed, bits=bits, d=d),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((_padded(b), d), jnp.float32),
        interpret=interpret,
    )
    return fn(ids.astype(jnp.int32), packed, step2d)[:b]
