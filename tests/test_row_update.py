"""``lpt.sparse_apply``'s row write-back: gather -> row Adam + SR -> scatter.

The write-back is XLA's batched row gather, the elementwise update over the
``K`` deduplicated rows, and one row scatter per leaf.  These tests hold it
to an independent NumPy oracle (same SR noise), to the rows it must leave
alone (the dedup sentinel's row among them), and to the form of its scatters;
and they pin the Pallas calls that remain in the jitted ALPT step.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lpt, quant
from repro.core.codestore import CodeStore

#: Live id space and allocated rows: row 19 is the scratch row that
#: ``pad_to_tiles`` puts past the id space; rows 20-23 are tile padding.
#: The write-back writes none of them.
N_LIVE, N_ALLOC, D = 19, 24, 16
#: No id more than twice, so the segment-sum's order cannot round.
IDS = np.array([[0, 5, 5, 18, 2], [7, 11, 3, 12, 0]], np.int32)
LR = 0.01


def _table(bits, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    table = lpt.init_table(
        ks[0], N_ALLOC, D, bits, init_scale=0.05, optimizer="adam",
        packed=bits < 8,
    )
    assert isinstance(table.codes, CodeStore)
    assert table.codes.packed == (bits < 8)
    return table._replace(
        mu=jax.random.normal(ks[1], (N_ALLOC, D)) * 0.01,
        nu=jax.random.uniform(ks[2], (N_ALLOC, D)) * 1e-3,
        count=jnp.int32(6),
    )


def _apply(table, ids, bits, *, weight_decay=0.0, new_step=None, seed=1,
           return_updated_rows=False):
    kg, kn = jax.random.split(jax.random.PRNGKey(seed))
    g_rows = jax.random.normal(kg, ids.shape + (D,)) * 0.1
    out = lpt.sparse_apply(
        table, jnp.asarray(ids), g_rows, lr=jnp.float32(LR), bits=bits,
        noise_key=kn, weight_decay=weight_decay, new_step=new_step,
        id_space=N_LIVE, return_updated_rows=return_updated_rows,
    )
    return out, g_rows, kn


def _oracle(table, ids, g_rows, key, *, bits, weight_decay):
    """Row Adam (decoupled decay) + SR, in NumPy float32."""
    codes = np.asarray(table.codes)
    step = np.asarray(table.step)
    flat = ids.reshape(-1)
    uniq = np.unique(flat)
    g_sum = np.zeros((uniq.size, D), np.float32)
    np.add.at(g_sum, np.searchsorted(uniq, flat),
              np.asarray(g_rows, np.float32).reshape(-1, D))
    t = np.float32(int(table.count) + 1)
    b1, b2, eps = np.float32(0.9), np.float32(0.999), np.float32(1e-8)
    w = codes[uniq].astype(np.float32) * step[uniq, None]
    mu = b1 * np.asarray(table.mu)[uniq] + np.float32(1.0 - 0.9) * g_sum
    nu = b2 * np.asarray(table.nu)[uniq] + np.float32(1.0 - 0.999) * g_sum**2
    upd = (mu / (np.float32(1) - b1**t)) / (
        np.sqrt(nu / (np.float32(1) - b2**t)) + eps
    )
    if weight_decay:
        upd = upd + np.float32(weight_decay) * w
    w_new = w - np.float32(LR) * upd
    # sparse_apply draws one noise row per dedup slot; the live ids come
    # first, sorted.
    noise = np.asarray(quant.sr_noise(key, (flat.size, D)))[: uniq.size]
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    scaled = np.clip(w_new / step[uniq, None], lo, hi)
    base = np.floor(scaled)
    q = np.clip(base + (scaled - base > noise), lo, hi).astype(np.int8)
    return uniq, q, mu, nu


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("weight_decay", [0.0, 5e-8])
def test_live_rows_match_numpy_oracle(bits, weight_decay):
    table = _table(bits)
    out, g_rows, key = _apply(table, IDS, bits, weight_decay=weight_decay)
    uniq, codes, mu, nu = _oracle(
        table, IDS, g_rows, key, bits=bits, weight_decay=weight_decay
    )
    assert out.codes.packed == (bits < 8) and out.codes.bits == bits
    np.testing.assert_array_equal(np.asarray(out.codes)[uniq], codes)
    np.testing.assert_allclose(np.asarray(out.mu)[uniq], mu, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out.nu)[uniq], nu, rtol=1e-6)
    assert int(out.count) == int(table.count) + 1
    # No caller-supplied step: the step vector is the input's, not a copy
    # rewritten with its own rows.
    assert out.step is table.step


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_rows_outside_the_batch_bit_identical(bits):
    table = _table(bits, seed=2)
    out, _, _ = _apply(table, IDS, bits, seed=3)
    rest = np.setdiff1d(np.arange(N_ALLOC), IDS)
    # Container bytes: packed rows own their bytes, so a row's bytes compare.
    np.testing.assert_array_equal(
        np.asarray(out.codes.data)[rest], np.asarray(table.codes.data)[rest]
    )
    for leaf in ("step", "mu", "nu"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out, leaf))[rest],
            np.asarray(getattr(table, leaf))[rest],
        )
    touched = np.unique(IDS)
    assert (np.asarray(out.mu)[touched] != np.asarray(table.mu)[touched]).all()


@pytest.mark.parametrize("bits", [4, 8])
def test_repeated_sentinels_write_no_row(bits):
    """Four live ids among eight lookups: the four other dedup slots hold
    the sentinel, and their writes drop; the scratch row past the id space,
    the tile padding and every row outside the batch stay as they were."""
    ids = np.array([[4, 9, 16, 2], [16, 9, 4, 2]], np.int32)
    table = _table(bits, seed=4)
    (out, (rows, _)), g_rows, key = _apply(
        table, ids, bits, seed=5, return_updated_rows=True
    )
    uniq, codes, mu, _ = _oracle(
        table, ids, g_rows, key, bits=bits, weight_decay=0.0
    )
    np.testing.assert_array_equal(
        np.asarray(rows), np.append(uniq, [N_ALLOC] * (ids.size - uniq.size))
    )
    np.testing.assert_array_equal(np.asarray(out.codes)[uniq], codes)
    np.testing.assert_allclose(np.asarray(out.mu)[uniq], mu, rtol=1e-6)
    rest = np.setdiff1d(np.arange(N_ALLOC), uniq)
    assert N_LIVE in rest
    np.testing.assert_array_equal(
        np.asarray(out.codes.data)[rest], np.asarray(table.codes.data)[rest]
    )
    for leaf in ("step", "mu", "nu"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out, leaf))[rest],
            np.asarray(getattr(table, leaf))[rest],
        )


def test_new_step_rows_are_written_and_requantized_with():
    """ALPT-style caller: the live rows take the given step and their codes
    are rounded against it; no other row's step changes."""
    bits = 8
    table = _table(bits, seed=6)
    k = IDS.size
    new_step = jnp.linspace(0.01, 0.02, k, dtype=jnp.float32)
    out, g_rows, key = _apply(table, IDS, bits, new_step=new_step, seed=7)
    uniq = np.unique(IDS)
    np.testing.assert_array_equal(
        np.asarray(out.step)[uniq], np.asarray(new_step)[: uniq.size]
    )
    rest = np.setdiff1d(np.arange(N_ALLOC), uniq)
    np.testing.assert_array_equal(
        np.asarray(out.step)[rest], np.asarray(table.step)[rest]
    )
    # Same float rows, rounded against the new step.
    w_new = lpt.sparse_apply(
        table, jnp.asarray(IDS), g_rows, lr=jnp.float32(LR), bits=bits,
        noise_key=key, id_space=N_LIVE, return_updated_rows=True,
    )[1][1]
    noise = quant.sr_noise(key, (k, D))
    expect = quant.quantize_codes(w_new, new_step, bits, "sr", noise)
    np.testing.assert_array_equal(
        np.asarray(out.codes)[uniq], np.asarray(expect)[: uniq.size]
    )


def test_write_back_ops_carry_the_row_update_scope():
    """The gather -> update -> scatter sits under ``lpt.row_update`` in the
    ops' metadata, where a device trace finds it; every table scatter says
    its indices are sorted, and none says they are unique."""
    table = _table(8)
    ids = jnp.asarray(IDS)
    g = jnp.zeros(IDS.shape + (D,), jnp.float32)

    def wb(table, g):
        return lpt.sparse_apply(
            table, ids, g, lr=jnp.float32(LR), bits=8,
            noise_key=jax.random.PRNGKey(0), id_space=N_LIVE,
        )

    text = jax.jit(wb).lower(table, g).as_text(debug_info=True)
    assert "lpt.row_update" in text
    heads = re.findall(r'"stablehlo\.scatter"\((%arg\d+)[^\n]*', text)
    # codes, mu, nu: the step is not rewritten.
    assert len(heads) == 3, heads
    for line in re.findall(r'"stablehlo\.scatter"\(%arg\d+[^\n]*', text):
        assert "indices_are_sorted = true" in line
        assert "unique_indices = false" in line


@pytest.mark.parametrize("bits", [8, 4])
def test_ctr_fused_step_runs_only_lookup_and_sr_kernels(bits):
    """The jitted ALPT CTR step holds Pallas calls for its two row lookups
    (``dequant_gather``) and for Algorithm 1 line 5's requantize
    (``sr_round``), and none for the row write-back."""
    from repro.analysis.jaxpr import _subjaxprs
    from test_codestore import _ctr_fixture

    def owners(jaxpr, owner=None):
        for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
            name = eqn.params.get("name") if eqn.primitive.name in (
                "pjit", "jit") else None
            if eqn.primitive.name == "pallas_call":
                yield owner
            for sub in _subjaxprs(eqn):
                yield from owners(sub, name or owner)

    tr, data, _ = _ctr_fixture("alpt", bits=bits, packed=bits < 8)
    state = tr.init_state()
    ids, labels = data.batch("train", 0, 16)
    jaxpr = jax.make_jaxpr(tr._train_step)(
        state, jnp.asarray(ids), jnp.asarray(labels)
    )
    gather = "_dequant_gather_jit" if bits == 8 else "_dequant_gather_packed_jit"
    assert sorted(owners(jaxpr)) == sorted([gather, gather, "_sr_round_jit"])
