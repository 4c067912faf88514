"""Pure-jnp oracles for every Pallas kernel (bit-exact where noise is shared).

The oracles are also the *fallback* implementations the ``ops`` wrappers run
on shape-misaligned inputs, so each one mirrors its kernel's exact operation
sequence (same association, no re-ordered reductions): kernels-on and
kernels-off must agree bitwise, not just to tolerance.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.codestore import pack_codes, unpack_codes


def dequant_gather_ref(codes: jax.Array, step: jax.Array, ids: jax.Array) -> jax.Array:
    rows = jnp.take(codes, ids, axis=0).astype(jnp.float32)
    return rows * jnp.take(step, ids)[:, None]


# Packed-container oracles: pack/unpack is exactly invertible on the valid
# code range and every arithmetic statement runs on the *unpacked* values in
# the same order as the unpacked oracle, so packed-on == packed-off bitwise.


def dequant_gather_packed_ref(packed, step, ids, *, bits: int, d: int):
    rows = unpack_codes(
        jnp.take(packed, ids, axis=0), bits, d
    ).astype(jnp.float32)
    return rows * jnp.take(step, ids)[:, None]


def dequant_matmul_packed_ref(x, packed, step, *, bits: int, k: int,
                              out_dtype=jnp.float32):
    return dequant_matmul_ref(
        x, unpack_codes(packed, bits, k), step, out_dtype
    )


def lpt_fused_update_packed_ref(packed, step, grad, noise, lr, bits: int,
                                d: int, new_step=None,
                                weight_decay: float = 0.0):
    codes_new = lpt_fused_update_ref(
        unpack_codes(packed, bits, d), step, grad, noise, lr, bits,
        new_step=new_step, weight_decay=weight_decay,
    )
    return pack_codes(codes_new, bits)


def sr_round_ref(w: jax.Array, step: jax.Array, noise: jax.Array, bits: int) -> jax.Array:
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    scaled = jnp.clip(w.astype(jnp.float32) / step[:, None], lo, hi)
    base = jnp.floor(scaled)
    up = (scaled - base > noise).astype(jnp.float32)
    return jnp.clip(base + up, lo, hi).astype(jnp.int8)


def dequant_matmul_ref(
    x: jax.Array, codes: jax.Array, step: jax.Array, out_dtype=jnp.float32
) -> jax.Array:
    w = codes.astype(jnp.float32) * step[:, None]
    return jnp.dot(x.astype(jnp.float32), w.T).astype(out_dtype)


def lpt_fused_update_ref(
    codes: jax.Array, step: jax.Array, grad: jax.Array, noise: jax.Array,
    lr, bits: int, new_step: jax.Array | None = None,
    weight_decay: float = 0.0,
) -> jax.Array:
    """Eq. (8): dequantize -> (decayed) SGD step -> SR re-quantize.

    ``grad`` is the already-formed update *direction* (the raw gradient for
    SGD, the bias-corrected Adam direction for the row-Adam path);
    ``weight_decay`` adds the decoupled ``wd * w`` term against the
    de-quantized weights, matching ``lpt._row_update``'s sequence exactly.
    """
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    # Two statements (dequantize, then update) — the same association as the
    # unfused core path and the kernel body, so XLA's FMA formation cannot
    # diverge between them.
    w = codes.astype(jnp.float32) * step[:, None]
    upd = grad.astype(jnp.float32)
    if weight_decay:
        upd = upd + weight_decay * w
    w = w - lr * upd
    ns = (step if new_step is None else new_step)[:, None]
    scaled = jnp.clip(w / ns, lo, hi)
    base = jnp.floor(scaled)
    up = (scaled - base > noise).astype(jnp.float32)
    return jnp.clip(base + up, lo, hi).astype(jnp.int8)
