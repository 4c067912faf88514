"""Pallas TPU kernel: fused CTR sparse embedding step (paper Eq. 8, row form).

One ``pallas_call`` over the batch's *unique* rows fuses the whole
``lpt.sparse_apply`` hot loop:

    gather int8 codes + Adam slots  ->  de-quantize  ->  Adam row step
    ->  SR re-quantize  ->  scatter codes/slots back in place

The scalar-prefetched unique ids drive both the input and the output
``BlockSpec`` index maps, so each grid step works on the row group that holds
one touched row (``row_blocks.GROUP`` rows; Mosaic refuses single-row
blocks) and writes the group back (``input_output_aliases`` -- the scatter
is the aliased write, not a separate XLA scatter).  Per touched row the HBM
traffic is its group's codes and Adam slots in and out, plus 4 B per element
of the grad and noise operands -- the de-quantized fp32 rows and the
intermediate ``w``/``w_new`` never exist in HBM.  The updated float rows are
emitted as a dense [K, d] output because ALPT's Delta sub-step (Algorithm 1
line 4) re-reads them.

Sentinel handling: ``jnp.unique(size=)`` pads with an out-of-range sentinel.
The caller must point sentinels at a dedicated *scratch row* (the
``pad_to_tiles`` policy allocates one past the id space) — sentinel steps then
read/write only that dead row, so duplicate sentinel writes cannot corrupt
live state under the TPU DMA pipeline.

Read-after-write: the ids are sorted before the launch, so ids that share a
row group are consecutive grid steps.  The group's output block then stays in
VMEM across them and each step updates the rows its predecessors wrote; the
pipeline never re-reads a group from HBM after writing it back.

Adam bias corrections ``c1 = 1 - b1^t`` / ``c2 = 1 - b2^t`` are computed by
the caller (they are per-step scalars) and prefetched to SMEM with ``lr``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.row_blocks import (
    GROUP, OUT_ROWS, pack_block, put_row, select_row, unpack_block,
)


def _kernel(ids_ref, scal_ref, codes_ref, step_ref, mu_ref, nu_ref, g_ref,
            noise_ref, out_codes, out_mu, out_nu, out_w, *,
            lo: int, hi: int, weight_decay: float, b1: float, b2: float,
            eps: float, bits: int = 8, d: int = 0):
    i = pl.program_id(0)
    grp = ids_ref[i] // GROUP

    # The table-shaped outputs are aliased onto their inputs, and a group's
    # output block stays in VMEM while consecutive ids share the group.  The
    # sorted ids visit each group in one run, so the run's first step seeds
    # the block from HBM and every later step reads its predecessors' rows.
    @pl.when((i == 0) | (grp != ids_ref[jnp.maximum(i - 1, 0)] // GROUP))
    def _():
        out_codes[...] = codes_ref[...]
        out_mu[...] = mu_ref[...]
        out_nu[...] = nu_ref[...]

    r = ids_ref[i] % GROUP  # the id's row within its group
    q = i % OUT_ROWS  # the id's row within the per-id blocks
    lr = scal_ref[0]
    c1 = scal_ref[1]
    c2 = scal_ref[2]
    packed = d > 0  # packed container: codes blocks are uint8 [GROUP, w]
    if packed:
        group_codes = unpack_block(out_codes[...], bits, d)
    else:
        group_codes = out_codes[...].astype(jnp.int32)
    step = select_row(step_ref[...], r)
    w = select_row(group_codes, r).astype(jnp.float32) * step
    g = select_row(g_ref[...], q)
    mu = b1 * select_row(out_mu[...], r) + (1.0 - b1) * g
    nu = b2 * select_row(out_nu[...], r) + (1.0 - b2) * jnp.square(g)
    upd = (mu / c1) / (jnp.sqrt(nu / c2) + eps)
    if weight_decay:
        upd = upd + weight_decay * w
    w_new = w - lr * upd
    scaled = jnp.clip(w_new / step, lo, hi)
    base = jnp.floor(scaled)
    up = (scaled - base > select_row(noise_ref[...], q)).astype(jnp.float32)
    codes_new = jnp.clip(base + up, lo, hi).astype(jnp.int32)
    group_codes = put_row(group_codes, r, codes_new)
    # Re-pack on the aliased scatter: the updated group leaves VMEM as packed
    # bytes, so the HBM write stays at bits/8 bytes per code.
    if packed:
        out_codes[...] = pack_block(group_codes, bits, out_codes.shape[-1])
    else:
        out_codes[...] = group_codes.astype(jnp.int8)
    out_mu[...] = put_row(out_mu[...], r, mu)
    out_nu[...] = put_row(out_nu[...], r, nu)
    out_w[...] = put_row(out_w[...], q, w_new)


def _call(codes, step, mu, nu, uniq, g_sum, noise, lr, c1, c2, *, bits, d,
          weight_decay, b1, b2, eps, interpret):
    """Shared launch of :func:`_kernel` for the int8 and packed containers.

    ``d`` is the logical width (0 for the int8 container).  The ids are
    sorted first -- the per-id operands move with them and ``w_new_rows``
    moves back -- so every row group is visited in one run of steps.
    """
    n, width = codes.shape
    dm = mu.shape[1]
    k = uniq.shape[0]
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    order = jnp.argsort(uniq)
    k_pad = -(-k // OUT_ROWS) * OUT_ROWS
    g_sorted = jnp.pad(g_sum[order], ((0, k_pad - k), (0, 0)))
    noise_sorted = jnp.pad(noise[order], ((0, k_pad - k), (0, 0)))

    def group(i, ids, s):
        return (ids[i] // GROUP, 0)

    def per_id(i, ids, s):
        return (i // OUT_ROWS, 0)

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # (sorted ids, [lr, c1, c2])
        grid=(k,),
        in_specs=[
            pl.BlockSpec((GROUP, width), group),
            pl.BlockSpec((GROUP, 1), group),
            pl.BlockSpec((GROUP, dm), group),
            pl.BlockSpec((GROUP, dm), group),
            pl.BlockSpec((OUT_ROWS, dm), per_id),
            pl.BlockSpec((OUT_ROWS, dm), per_id),
        ],
        out_specs=[
            pl.BlockSpec((GROUP, width), group),
            pl.BlockSpec((GROUP, dm), group),
            pl.BlockSpec((GROUP, dm), group),
            pl.BlockSpec((OUT_ROWS, dm), per_id),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(
            _kernel, lo=lo, hi=hi, weight_decay=weight_decay, b1=b1, b2=b2,
            eps=eps, bits=bits, d=d,
        ),
        grid_spec=spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, width), codes.dtype),
            jax.ShapeDtypeStruct((n, dm), jnp.float32),
            jax.ShapeDtypeStruct((n, dm), jnp.float32),
            jax.ShapeDtypeStruct((k_pad, dm), jnp.float32),
        ],
        # Operand indices count the scalar-prefetch args: 2=codes, 4=mu, 5=nu.
        input_output_aliases={2: 0, 4: 1, 5: 2},
        interpret=interpret,
    )
    scal = jnp.stack(
        [jnp.asarray(lr, jnp.float32), jnp.asarray(c1, jnp.float32),
         jnp.asarray(c2, jnp.float32)]
    )
    codes2, mu2, nu2, w_sorted = fn(
        uniq[order].astype(jnp.int32), scal, codes, step.reshape(n, 1), mu,
        nu, g_sorted, noise_sorted,
    )
    w_new = jnp.zeros((k, dm), jnp.float32).at[order].set(w_sorted[:k])
    return codes2, mu2, nu2, w_new


def sparse_row_update(
    codes: jax.Array,  # int8 [N, d] (N > every id in uniq, incl. sentinels)
    step: jax.Array,  # f32 [N]
    mu: jax.Array,  # f32 [N, d] Adam first moment
    nu: jax.Array,  # f32 [N, d] Adam second moment
    uniq: jax.Array,  # int32 [K] unique ids; sentinels mapped to a scratch row
    g_sum: jax.Array,  # f32 [K, d] summed per-unique-row gradients
    noise: jax.Array,  # f32 [K, d] uniform [0,1)
    lr: jax.Array,  # f32 scalar
    c1: jax.Array,  # f32 scalar 1 - b1^t
    c2: jax.Array,  # f32 scalar 1 - b2^t
    bits: int,
    *,
    weight_decay: float = 0.0,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    interpret: bool = False,
):
    """Returns ``(codes', mu', nu', w_new_rows)`` — table-shaped outputs are
    the aliased in-place scatters; ``w_new_rows`` is [K, d] f32."""
    return _call(
        codes, step, mu, nu, uniq, g_sum, noise, lr, c1, c2, bits=bits, d=0,
        weight_decay=weight_decay, b1=b1, b2=b2, eps=eps, interpret=interpret,
    )


def sparse_row_update_packed(
    packed: jax.Array,  # uint8 [N, w] packed container (w = ceil(d*bits/8))
    step: jax.Array,  # f32 [N]
    mu: jax.Array,  # f32 [N, d]
    nu: jax.Array,  # f32 [N, d]
    uniq: jax.Array,  # int32 [K]
    g_sum: jax.Array,  # f32 [K, d]
    noise: jax.Array,  # f32 [K, d]
    lr: jax.Array,
    c1: jax.Array,
    c2: jax.Array,
    bits: int,
    d: int,
    *,
    weight_decay: float = 0.0,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    interpret: bool = False,
):
    """Packed-container twin of :func:`sparse_row_update`.

    Each grid step DMAs one packed uint8 row group (w bytes per row) in,
    unpacks it in VMEM, runs the identical Adam + SR body on the id's codes,
    re-packs, and writes the group back through the same
    ``input_output_aliases`` scatter -- bits/8 bytes per code of HBM code
    traffic in each direction.  Returns
    ``(packed', mu', nu', w_new_rows)``.
    """
    return _call(
        packed, step, mu, nu, uniq, g_sum, noise, lr, c1, c2, bits=bits, d=d,
        weight_decay=weight_decay, b1=b1, b2=b2, eps=eps, interpret=interpret,
    )
