"""Per-kernel allclose tests vs the jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels import ref
from repro.kernels.dequant_gather import dequant_gather
from repro.kernels.dequant_matmul import dequant_matmul
from repro.kernels.sr_round import sr_round, sr_round_seeded
from repro.kernels import ops

jax.config.update("jax_platform_name", "cpu")

I = dict(interpret=True)


# ------------------------------------------------------------ dequant_gather


@pytest.mark.parametrize(
    "n,d,b,d_block",
    [
        (32, 16, 8, 16),
        (128, 128, 64, 128),
        (1000, 256, 37, 128),
        (64, 512, 128, 512),
    ],
)
def test_dequant_gather_matches_ref(n, d, b, d_block):
    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    codes = jax.random.randint(k1, (n, d), -128, 128, jnp.int8)
    step = jax.random.uniform(k2, (n,), minval=1e-3, maxval=0.1)
    ids = jax.random.randint(k3, (b,), 0, n, jnp.int32)
    out = dequant_gather(codes, step, ids, d_block=d_block, **I)
    expect = ref.dequant_gather_ref(codes, step, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-6)


def test_dequant_gather_repeated_ids():
    codes = jnp.arange(64, dtype=jnp.int8).reshape(4, 16)
    step = jnp.array([1.0, 0.5, 0.25, 2.0])
    ids = jnp.array([2, 2, 2, 0], jnp.int32)
    out = dequant_gather(codes, step, ids, d_block=16, **I)
    expect = ref.dequant_gather_ref(codes, step, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect))


# ------------------------------------------------------------ sr_round


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("shape", [(8, 16), (256, 512), (64, 1024), (512, 128)])
def test_sr_round_matches_ref_bit_exact(bits, shape):
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    w = jax.random.normal(k1, shape) * 0.05
    step = jax.random.uniform(k2, (shape[0],), minval=1e-3, maxval=0.05)
    noise = jax.random.uniform(k3, shape)
    rb, cb = min(256, shape[0]), min(512, shape[1])
    out = sr_round(w, step, noise, bits, row_block=rb, col_block=cb, **I)
    expect = ref.sr_round_ref(w, step, noise, bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_sr_round_matches_core_quant():
    """Kernel == quant.quantize_codes (the semantics LPT depends on)."""
    key = jax.random.PRNGKey(2)
    w = jax.random.normal(key, (32, 64)) * 0.1
    step = jnp.full((32,), 0.01)
    noise = jax.random.uniform(jax.random.PRNGKey(3), (32, 64))
    out = sr_round(w, step, noise, 8, row_block=32, col_block=64, **I)
    expect = quant.quantize_codes(w, step, 8, "sr", noise)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_sr_round_seeded_lowers_and_is_on_lattice():
    """On-chip PRNG variant (production TPU path).

    The CPU TPU-interpreter stubs ``prng_random_bits`` to zeros, so the noise
    *distribution* can only be validated on real TPU hardware; here we verify
    the kernel lowers under TPU-semantics interpretation and that every output
    is one of the two adjacent lattice codes (the SR invariant that holds for
    ANY noise realization).
    """
    from jax.experimental.pallas import tpu as pltpu

    w = jnp.full((16, 128), 0.0155)
    step = jnp.full((16,), 0.01)
    out = sr_round_seeded(
        w, step, jnp.asarray(42), 8, row_block=16, col_block=128,
        interpret=pltpu.InterpretParams(),
    )
    vals = np.asarray(out)
    assert set(np.unique(vals)).issubset({1, 2})  # floor/ceil of 1.55 only


# ------------------------------------------------------------ dequant_matmul


@pytest.mark.parametrize(
    "m,n,k,bm,bn,bk",
    [
        (8, 16, 32, 8, 16, 32),
        (128, 128, 128, 128, 128, 128),
        (128, 256, 512, 128, 128, 128),
        (256, 128, 1024, 128, 128, 512),
    ],
)
@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
def test_dequant_matmul_matches_ref(m, n, k, bm, bn, bk, x_dtype):
    key = jax.random.PRNGKey(5)
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (m, k), x_dtype)
    codes = jax.random.randint(k2, (n, k), -128, 128, jnp.int8)
    step = jax.random.uniform(k3, (n,), minval=1e-3, maxval=0.02)
    out = dequant_matmul(x, codes, step, block_m=bm, block_n=bn, block_k=bk, **I)
    expect = ref.dequant_matmul_ref(x, codes, step)
    # Tolerances cover accumulation-order differences (blocked K vs one dot).
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expect),
        rtol=2e-2 if x_dtype == jnp.bfloat16 else 1e-4,
        atol=2e-1 if x_dtype == jnp.bfloat16 else 1e-3,
    )


def test_dequant_matmul_equals_dequant_then_matmul():
    """Fusion must not change semantics vs materialize-then-matmul."""
    x = jax.random.normal(jax.random.PRNGKey(6), (16, 64))
    codes = jax.random.randint(jax.random.PRNGKey(7), (32, 64), -128, 128, jnp.int8)
    step = jnp.full((32,), 0.01)
    fused = dequant_matmul(x, codes, step, block_m=16, block_n=32, block_k=64, **I)
    table = quant.dequantize(codes, step)
    unfused = x @ table.T
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(unfused), rtol=1e-4, atol=1e-5
    )


# ------------------------------------------------------------ ops wrappers


def test_ops_fallback_on_unaligned():
    """Non-divisible shapes use the oracle — same numbers, counted fallback."""
    codes = jax.random.randint(jax.random.PRNGKey(8), (10, 7), -128, 128, jnp.int8)
    step = jnp.full((10,), 0.02)
    ids = jnp.array([0, 3, 9], jnp.int32)
    out = ops.dequant_gather(codes, step, ids)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.dequant_gather_ref(codes, step, ids))
    )


def test_fallback_stats_odd_dim_reported_aligned_not():
    """Satellite contract: an odd-dim table reports a shape fallback, an
    aligned one reports a kernel hit and NO fallback (never silent)."""
    ops.reset_fallback_stats()
    step = jnp.full((24,), 0.02)
    ids = jnp.array([1, 5], jnp.int32)
    # Odd dim (d=9 is not a sublane multiple) -> counted fallback.
    odd = jax.random.randint(jax.random.PRNGKey(20), (24, 9), -128, 128, jnp.int8)
    ops.dequant_gather(odd, step, ids)
    stats = ops.fallback_stats()
    assert stats["total_fallbacks"] == 1
    assert stats["fallbacks"][0]["op"] == "dequant_gather"
    assert "sublane" in stats["fallbacks"][0]["reason"]
    # Aligned dim -> kernel path, fallback count unchanged.
    aligned = jax.random.randint(jax.random.PRNGKey(21), (24, 16), -128, 128, jnp.int8)
    ops.dequant_gather(aligned, step, ids)
    stats = ops.fallback_stats()
    assert stats["total_fallbacks"] == 1
    assert stats["kernel_calls"].get("dequant_gather", 0) >= 1
    ops.reset_fallback_stats()
    assert ops.fallback_stats()["total_fallbacks"] == 0


def test_fallback_stats_sr_round_misaligned_rows():
    ops.reset_fallback_stats()
    w = jax.random.normal(jax.random.PRNGKey(22), (13, 16)) * 0.05
    step = jnp.full((13,), 0.01)
    noise = jax.random.uniform(jax.random.PRNGKey(23), (13, 16))
    out = ops.sr_round(w, step, noise, 8)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ref.sr_round_ref(w, step, noise, 8))
    )
    assert ops.fallback_stats()["total_fallbacks"] == 1
    ops.reset_fallback_stats()


def test_fallback_scope_reports_despite_prior_trace():
    """Satellite contract (PR 5): a scope sees every dispatch made while it
    is active — including shapes the process already traced and reset away,
    which the old reset-then-read dance in launch/serve.py under-reported."""
    ops.reset_fallback_stats()
    step = jnp.full((24,), 0.02)
    ids = jnp.array([1, 5], jnp.int32)
    odd = jax.random.randint(jax.random.PRNGKey(30), (24, 9), -128, 128, jnp.int8)
    ops.dequant_gather(odd, step, ids)  # compiled + counted globally
    assert ops.fallback_stats()["total_fallbacks"] == 1
    ops.reset_fallback_stats()  # the historical dance: reset...
    with ops.fallback_scope() as scope:
        ops.dequant_gather(odd, step, ids)  # ...same shapes, already compiled
    # ...and the scope still reports the fallback the dispatch actually hit.
    assert scope.stats()["total_fallbacks"] == 1
    assert scope.stats()["fallbacks"][0]["op"] == "dequant_gather"
    # Dispatches outside the scope are not attributed to it.
    ops.dequant_gather(odd, step, ids)
    assert scope.stats()["total_fallbacks"] == 1
    # Re-entering an existing scope accumulates (the Engine's usage).
    aligned = jax.random.randint(jax.random.PRNGKey(31), (24, 16), -128, 128,
                                 jnp.int8)
    with ops.fallback_scope(scope):
        ops.dequant_gather(aligned, step, ids)
    assert scope.stats()["kernel_calls"].get("dequant_gather", 0) == 1
    assert scope.stats()["total_fallbacks"] == 1
    ops.reset_fallback_stats()


def test_ops_jit_wrappers_run():
    w = jax.random.normal(jax.random.PRNGKey(9), (256, 512)) * 0.1
    step = jnp.full((256,), 0.01)
    noise = jax.random.uniform(jax.random.PRNGKey(10), (256, 512))
    codes = ops.sr_round(w, step, noise, 8)
    assert codes.dtype == jnp.int8
    x = jax.random.normal(jax.random.PRNGKey(11), (128, 512))
    y = ops.dequant_matmul(x, codes, step)
    assert y.shape == (128, 256)
    got = ops.dequant_gather(codes, step, jnp.arange(64, dtype=jnp.int32))
    assert got.shape == (64, 512)


# ------------------------------------------------------------ lpt_fused_update


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("shape,rb,cb", [((32, 64), 32, 64), ((256, 512), 256, 512),
                                         ((512, 1024), 256, 512)])
def test_lpt_fused_update_matches_ref(bits, shape, rb, cb):
    from repro.kernels.lpt_update import lpt_fused_update

    key = jax.random.PRNGKey(11)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    codes = jax.random.randint(k1, shape, -(2**(bits-1)), 2**(bits-1), jnp.int8)
    step = jax.random.uniform(k2, (shape[0],), minval=1e-3, maxval=0.05)
    grad = jax.random.normal(k3, shape) * 0.1
    noise = jax.random.uniform(k4, shape)
    out = lpt_fused_update(codes, step, grad, noise, 0.01, bits,
                           row_block=rb, col_block=cb, interpret=True)
    expect = ref.lpt_fused_update_ref(codes, step, grad, noise, 0.01, bits)
    # SR compares frac(w/Delta) against the noise draw; when they agree to
    # ~1 ULP the fused fma ordering may round the comparison the other way.
    # Allow <=0.01% knife-edge ties, never more than one lattice step apart.
    diff = np.asarray(out).astype(np.int32) - np.asarray(expect).astype(np.int32)
    assert np.abs(diff).max() <= 1
    assert (diff != 0).mean() < 1e-4


def test_lpt_fused_update_with_new_step_matches_core():
    """Fused kernel == the unfused core path (dequant -> sgd -> SR requant),
    including ALPT's Delta' requantize (Algorithm 1 line 5)."""
    from repro.kernels.lpt_update import lpt_fused_update

    key = jax.random.PRNGKey(12)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    codes = jax.random.randint(k1, (64, 128), -128, 128, jnp.int8)
    step = jax.random.uniform(k2, (64,), minval=1e-3, maxval=0.02)
    new_step = step * jax.random.uniform(k5, (64,), minval=0.8, maxval=1.2)
    grad = jax.random.normal(k3, (64, 128)) * 0.05
    noise = jax.random.uniform(k4, (64, 128))
    out = lpt_fused_update(codes, step, grad, noise, 0.01, 8,
                           new_step=new_step, row_block=64, col_block=128,
                           interpret=True)
    w = quant.dequantize(codes, step) - 0.01 * grad
    expect = quant.quantize_codes(w, new_step, 8, "sr", noise)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


# ------------------------------------------------------- packed containers
#
# The packed-storage contract: a CodeStore at bits in {2, 4} keeps its codes
# packed through every fused op — packed bytes move HBM->VMEM, the unpack
# (and the dense write-back's re-pack) happen in VMEM — and the results are BITWISE
# equal to the raw int8 path, kernels on or off.


def _packed_fixture(bits, n=32, d=16, seed=40):
    from repro.core import codestore

    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    raw = jax.random.randint(
        ks[0], (n, d), -(2 ** (bits - 1)), 2 ** (bits - 1), jnp.int8
    )
    step = jax.random.uniform(ks[1], (n,), minval=1e-3, maxval=0.05)
    store = codestore.CodeStore.from_codes(raw, bits)
    assert store.packed and store.data.dtype == jnp.uint8
    return raw, store, step


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("use_kernel", [True, False])
def test_packed_dequant_gather_bitwise(bits, use_kernel):
    raw, store, step = _packed_fixture(bits)
    ids = jnp.array([0, 5, 5, 31, 2, 17, 8, 30], jnp.int32)
    got = ops.dequant_gather(store, step, ids, use_kernel=use_kernel)
    expect = ops.dequant_gather(raw, step, ids, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("use_kernel", [True, False])
def test_packed_lpt_update_bitwise(bits, use_kernel):
    raw, store, step = _packed_fixture(bits)
    ks = jax.random.split(jax.random.PRNGKey(41), 2)
    grad = jax.random.normal(ks[0], raw.shape) * 0.05
    noise = jax.random.uniform(ks[1], raw.shape)
    got = ops.lpt_update(
        store, step, grad, noise, 0.01, bits, use_kernel=use_kernel
    )
    expect = ops.lpt_update(
        raw, step, grad, noise, 0.01, bits, use_kernel=False
    )
    assert got.bits == bits and got.packed  # layout preserved on write-back
    np.testing.assert_array_equal(
        np.asarray(got.unpack()), np.asarray(expect)
    )


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("use_kernel", [True, False])
def test_packed_dequant_matmul_bitwise(bits, use_kernel):
    raw, store, step = _packed_fixture(bits)
    x = jax.random.normal(jax.random.PRNGKey(43), (8, raw.shape[1]))
    got = ops.dequant_matmul(x, store, step, use_kernel=use_kernel)
    expect = ops.dequant_matmul(x, raw, step, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


@pytest.mark.parametrize("bits", [2, 4])
def test_packed_dispatch_counts_no_fallbacks(bits):
    """Packed dispatches land on the kernel path (counted under the same op
    names as unpacked — the 'never silent' contract) with zero fallbacks on
    aligned geometry."""
    raw, store, step = _packed_fixture(bits)
    ids = jnp.arange(16, dtype=jnp.int32)
    ops.reset_fallback_stats()
    ops.dequant_gather(store, step, ids)
    grad = jnp.zeros(raw.shape, jnp.float32)
    noise = jnp.full(raw.shape, 0.5)
    ops.lpt_update(store, step, grad, noise, 0.01, bits)
    stats = ops.fallback_stats()
    assert stats["total_fallbacks"] == 0, stats
    assert stats["kernel_calls"].get("dequant_gather", 0) >= 1
    assert stats["kernel_calls"].get("lpt_update", 0) >= 1
