"""Pallas TPU kernel: fused clip + stochastic-round + int8 pack (LPT write-back).

Implements Eq. (1)/(4): codes = SR(clip(w / Delta, -2^{m-1}, 2^{m-1}-1)).

Two noise sources:
  * ``sr_round``      — uniform noise passed as an operand.  Bit-exact against
    the jnp oracle, used everywhere correctness matters (and in CPU tests).
  * ``sr_round_seeded`` — on-chip ``pltpu.prng_random_bits`` seeded per tile;
    saves the noise operand's HBM traffic (the production TPU path).

The op is elementwise -> pure bandwidth; tiles are (row_block, col_block)
VMEM blocks, (8, 128)-aligned on real shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(w_ref, step_ref, noise_ref, out_ref, *, lo: int, hi: int):
    w = w_ref[...].astype(jnp.float32)
    step = step_ref[...].astype(jnp.float32)  # (rb, 1) broadcast over lanes
    scaled = jnp.clip(w / step, lo, hi)
    base = jnp.floor(scaled)
    up = (scaled - base > noise_ref[...]).astype(jnp.float32)
    out_ref[...] = jnp.clip(base + up, lo, hi).astype(jnp.int8)


def _kernel_seeded(seed_ref, w_ref, step_ref, out_ref, *, lo: int, hi: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    pltpu.prng_seed(seed_ref[0], i, j)
    w = w_ref[...].astype(jnp.float32)
    step = step_ref[...].astype(jnp.float32)
    scaled = jnp.clip(w / step, lo, hi)
    base = jnp.floor(scaled)
    bits = pltpu.prng_random_bits(w.shape)
    # uniform [0, 1) from the top 24 bits (exact float32 representation).
    u = (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    up = (scaled - base > u).astype(jnp.float32)
    out_ref[...] = jnp.clip(base + up, lo, hi).astype(jnp.int8)


def _blocks(rows: int, cols: int, row_block: int, col_block: int):
    rb = min(row_block, rows)
    cb = min(col_block, cols)
    if rows % rb or cols % cb:
        raise ValueError(f"shape ({rows},{cols}) not divisible by ({rb},{cb})")
    return rb, cb


def sr_round(
    w: jax.Array,  # f32 [r, c]
    step: jax.Array,  # f32 [r] per-row Delta
    noise: jax.Array,  # f32 [r, c] uniform [0,1)
    bits: int,
    *,
    row_block: int = 256,
    col_block: int = 512,
    interpret: bool = False,
) -> jax.Array:
    rows, cols = w.shape
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    rb, cb = _blocks(rows, cols, row_block, col_block)
    grid = (rows // rb, cols // cb)
    fn = pl.pallas_call(
        lambda a, b, c, o: _kernel(a, b, c, o, lo=lo, hi=hi),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, cb), lambda i, j: (i, j)),
            pl.BlockSpec((rb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((rb, cb), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((rb, cb), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.int8),
        interpret=interpret,
    )
    return fn(w, step.reshape(rows, 1), noise)


def sr_round_seeded(
    w: jax.Array,
    step: jax.Array,
    seed: jax.Array,  # int32 scalar
    bits: int,
    *,
    row_block: int = 256,
    col_block: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """On-chip PRNG variant (no noise operand -> 1/3 less input traffic)."""
    rows, cols = w.shape
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    rb, cb = _blocks(rows, cols, row_block, col_block)
    grid = (rows // rb, cols // cb)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rb, cb), lambda i, j, s: (i, j)),
            pl.BlockSpec((rb, 1), lambda i, j, s: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rb, cb), lambda i, j, s: (i, j)),
    )
    fn = pl.pallas_call(
        lambda s, a, b, o: _kernel_seeded(s, a, b, o, lo=lo, hi=hi),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((rows, cols), jnp.int8),
        interpret=interpret,
    )
    return fn(seed.reshape(1).astype(jnp.int32), w, step.reshape(rows, 1))
