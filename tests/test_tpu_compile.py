"""The main-path kernels compile for a TPU v5e at Criteo width.

Each test traces an ``ops`` wrapper with the dispatch rule a TPU backend
takes (compiled Pallas, no interpret mode) and compiles it for one chip of a
described ``v5e:2x2`` topology: nothing runs, but the chip's compiler accepts
or refuses every kernel as it would on the chip.  Shapes are the smoke
configuration's (``chip_smoke.py``): a 1,086,880 x 16 table and 4096 ids.

The topology is described inside a module fixture, never at import, so every
test worker collects the same tests and only the worker running this file
loads the TPU compiler.
"""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import lpt
from repro.core.codestore import CodeStore, packed_width
from repro.kernels import ops

N, D, B = 1_086_880, 16, 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    # A compile for a described chip is written to the cache but cannot be
    # read back without one; keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture
def compile_for_chip(one_chip, monkeypatch):
    """Compile ``fn`` over ShapeDtypeStructs for the described chip, with
    ``ops`` dispatching as on a TPU backend; returns the compiled text and
    the dispatch tally."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)

    def run(fn, *shapes, lowered=None):
        placed = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
            shapes,
        )
        with _no_persistent_cache(), ops.fallback_scope() as scope:
            low = jax.jit(fn).lower(*placed)
            text = low.compile().as_text()
        if lowered is not None:  # the program as traced, before XLA's passes
            lowered.append(low.as_text())
        return text, scope.stats()

    return run


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _codes(bits):
    if bits == 8:
        return jax.ShapeDtypeStruct((N, D), jnp.int8)
    data = jax.ShapeDtypeStruct((N, packed_width(D, bits)), jnp.uint8)
    return CodeStore(data=data, bits=bits, n=N, d=D, packed=True)


def _assert_kernel(op, text, stats):
    assert "tpu_custom_call" in text
    assert stats["total_fallbacks"] == 0, stats
    assert stats["kernel_calls"] == {op: 1}


@pytest.mark.parametrize("bits", [8, 4])
def test_dequant_gather_compiles_for_v5e(compile_for_chip, bits):
    text, stats = compile_for_chip(
        ops.dequant_gather, _codes(bits), _f32(N),
        jax.ShapeDtypeStruct((B,), jnp.int32),
    )
    _assert_kernel("dequant_gather", text, stats)


@pytest.mark.parametrize("bits", [8, 4])
def test_sparse_apply_compiles_for_v5e(compile_for_chip, bits):
    """The CTR row write-back (gather -> row Adam + SR -> scatter) for a
    B-id batch: its table scatters carry the K = B dedup slots, and neither
    Adam moment is relaid out or copied more than once (the copy that the
    un-donated state forces before the in-place scatter)."""
    def update(table, ids, g, lr, key):
        return lpt.sparse_apply(
            table, ids, g, lr=lr, bits=bits, noise_key=key,
            weight_decay=1e-5, id_space=N - 2,
        )

    table = lpt.LPTTable(
        codes=_codes(bits), step=_f32(N), mu=_f32(N, D), nu=_f32(N, D),
        count=jax.ShapeDtypeStruct((), jnp.int32),
    )
    lowered = []
    text, stats = compile_for_chip(
        update, table, jax.ShapeDtypeStruct((B,), jnp.int32), _f32(B, D),
        _f32(), jax.ShapeDtypeStruct((2,), jnp.uint32), lowered=lowered,
    )
    assert "tpu_custom_call" not in text
    assert stats["kernel_calls"] == {} and stats["total_fallbacks"] == 0
    # Scatters into table-shaped operands (leading dim N): codes, mu, nu.
    sig = re.findall(
        r"\}\) : \(tensor<(\d+)x[^,]*, tensor<\d+x1xi32>, tensor<(\d+)x",
        lowered[0],
    )
    table_scatters = [k for n, k in sig if int(n) == N]
    assert table_scatters == [str(B)] * 3, sig
    entry = text[text.index("ENTRY"):]
    moment = re.compile(rf"= \(?f32\[{N},{D}\]\{{([\d,]+):")
    layouts = {m.group(1) for m in moment.finditer(entry)}
    assert len(layouts) == 1, layouts  # the parameters' layout throughout
    copies = re.findall(rf"= f32\[{N},{D}\]\S* copy\(", entry)
    assert len(copies) <= 2, copies


def test_sr_round_compiles_for_v5e(compile_for_chip):
    text, stats = compile_for_chip(
        lambda w, step, noise: ops.sr_round(w, step, noise, 8),
        _f32(N, D), _f32(N), _f32(N, D),
    )
    _assert_kernel("sr_round", text, stats)


def test_lpt_update_compiles_for_v5e(compile_for_chip):
    text, stats = compile_for_chip(
        lambda codes, step, grad, noise, lr: ops.lpt_update(
            codes, step, grad, noise, lr, 8
        ),
        _codes(8), _f32(N), _f32(N, D), _f32(N, D), _f32(),
    )
    _assert_kernel("lpt_update", text, stats)
