"""Kernel microbenchmarks: Pallas (interpret on CPU) vs jnp reference.

On CPU the pallas interpreter is NOT representative of TPU speed — the
derived column therefore reports bytes moved and the arithmetic intensity the
BlockSpec tiling claims, which is what transfers to TPU.  The jnp reference
is additionally timed for a same-machine sanity number.
"""
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.kernels import ops, ref


def _time(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run():
    # One fresh subkey per array: no two benchmark inputs share a stream.
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 20))
    n, d, b = 100_000, 128, 4096
    codes = jax.random.randint(next(keys), (n, d), -128, 128, jnp.int8)
    step = jax.random.uniform(next(keys), (n,), minval=1e-3, maxval=0.1)
    ids = jax.random.randint(next(keys), (b,), 0, n, jnp.int32)
    us = _time(lambda *a: ops.dequant_gather(*a), codes, step, ids)
    us_ref = _time(lambda *a: ref.dequant_gather_ref(*a), codes, step, ids)
    moved = b * d * (1 + 4) + b * 4  # int8 in, f32 out
    emit("kernel/dequant_gather", us,
         f"ref_us={us_ref:.1f} bytes={moved} int8_vs_f32_read=4.0x")

    w = jax.random.normal(next(keys), (4096, 512)) * 0.05
    st = jax.random.uniform(next(keys), (4096,), minval=1e-3, maxval=0.05)
    noise = jax.random.uniform(next(keys), (4096, 512))
    us = _time(lambda *a: ops.sr_round(*a, 8), w, st, noise)
    us_ref = _time(lambda *a: ref.sr_round_ref(*a, 8), w, st, noise)
    emit("kernel/sr_round", us,
         f"ref_us={us_ref:.1f} bytes={4096*512*(4+4+1)} writeback_int8=4x_smaller")

    # Fused dense write-back (Eq. 8): codes in/out are the only table bytes.
    codes_sq = jax.random.randint(next(keys), (4096, 512), -128, 128, jnp.int8)
    grad = jax.random.normal(next(keys), (4096, 512)) * 0.1
    us = _time(
        lambda *a: ops.lpt_update(*a, 8), codes_sq, st, grad, noise,
        jnp.float32(0.01),
    )
    us_ref = _time(
        lambda *a: ref.lpt_fused_update_ref(*a, 0.01, 8), codes_sq, st, grad,
        noise,
    )
    fused_b = 4096 * 512 * (1 + 4 + 4 + 1)  # codes in, grad+noise in, codes out
    unfused_b = 4096 * 512 * (1 + 4 + 4 + 4 + 4 + 4 + 1)  # + 3 fp32 round-trips
    emit("kernel/lpt_update", us,
         f"ref_us={us_ref:.1f} bytes={fused_b} "
         f"unfused_bytes={unfused_b} traffic_saved={unfused_b/fused_b:.1f}x")

    x = jax.random.normal(next(keys), (256, 2048), jnp.bfloat16)
    wc = jax.random.randint(next(keys), (2048, 2048), -128, 128, jnp.int8)
    ws = jax.random.uniform(next(keys), (2048,), minval=1e-3, maxval=0.02)
    us = _time(lambda *a: ops.dequant_matmul(*a), x, wc, ws)
    us_ref = _time(lambda *a: ref.dequant_matmul_ref(*a), x, wc, ws)
    flops = 2 * 256 * 2048 * 2048
    wbytes = 2048 * 2048
    emit("kernel/dequant_matmul", us,
         f"ref_us={us_ref:.1f} flops={flops} weight_bytes={wbytes} "
         f"intensity={flops/wbytes:.0f}flop_per_weight_byte")


if __name__ == "__main__":
    run()
