"""Public wrappers over the Pallas kernels — the embedding hot-path API.

``interpret`` defaults to True off-TPU so the same call sites run everywhere;
on TPU the compiled kernels are used.  Off-TPU the elementwise kernels run
with whole-array blocks (one grid step): the tiled decomposition is a TPU
bandwidth concern, and per-tile interpretation on CPU would only add loop
overhead without changing a single bit of the result.

Alignment contract: a shape is kernel-eligible when every blocked dimension
is a multiple of 8 (the fp32 sublane granularity).  On TPU a block's last
dimension is a multiple of 128 or the whole axis, and the row kernels move
32-row groups (:mod:`repro.kernels.row_blocks`).  Non-eligible shapes fall
back to the bitwise-identical jnp reference in :mod:`repro.kernels.ref` —
*never silently*: every fallback is counted and logged once per distinct
(op, shape, reason), and :func:`fallback_stats` exposes the tally so
benchmarks and trainers can assert the hot path actually runs fused
(``EmbeddingSpec.pad_to_tiles`` is the knob that makes real table
geometries eligible).

Dispatch accounting happens when the *wrapper* runs: eagerly per call, or
once per trace when the call site sits inside an enclosing ``jit``.  The
wrappers themselves are plain Python over jitted inner implementations, so a
fresh consumer (a new jitted step function, a serving engine warming up) sees
its dispatch decisions counted even when the inner kernels were already
compiled earlier in the process — the old trace-time scheme silently skipped
those on jit-cache hits.  :func:`fallback_scope` scopes the same tally to a
``with`` block for consumers that need an accurate local report (the serving
Engine) without resetting the process-wide counters.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import logging

import jax
import jax.numpy as jnp

from repro.core.codestore import CodeStore
from repro.kernels import ref
from repro.obs import counters as obs_counters
from repro.storage import base as rowstore
from repro.storage.tiered import TieredCodes
from repro.kernels.dequant_gather import dequant_gather as _dequant_gather
from repro.kernels.dequant_gather import (
    dequant_gather_packed as _dequant_gather_packed,
)
from repro.kernels.dequant_matmul import dequant_matmul as _dequant_matmul
from repro.kernels.dequant_matmul import (
    dequant_matmul_packed as _dequant_matmul_packed,
)
from repro.kernels.lpt_update import lpt_fused_update as _lpt_fused_update
from repro.kernels.lpt_update import (
    lpt_fused_update_packed as _lpt_fused_update_packed,
)
from repro.kernels.sr_round import sr_round as _sr_round
from repro.kernels.sr_round import sr_round_seeded as sr_round_seeded  # re-export

logger = logging.getLogger("repro.kernels")

#: fp32 sublane granularity — every blocked dimension must divide into it.
SUBLANE = 8
#: Lane width: a block's last dimension is a multiple of it or the whole axis.
LANE = 128
#: Preferred (row, col) tile targets on TPU; interpret mode uses whole arrays.
ROW_BLOCK = 256
COL_BLOCK = 512

# ---------------------------------------------------------------- accounting


class FallbackScope:
    """One scoped tally of kernel-vs-fallback dispatch decisions.

    Created by :func:`fallback_scope`; while active it receives every
    dispatch note alongside the process-wide counters, so a consumer can
    report exactly the fallbacks *its* calls hit — independent of what the
    rest of the process traced before or since.
    """

    def __init__(self) -> None:
        self.kernel_calls: collections.Counter = collections.Counter()
        self.fallbacks: collections.Counter = collections.Counter()

    def stats(self) -> dict:
        return _stats_of(self.kernel_calls, self.fallbacks)


# Process-wide tallies live in the repro.obs registry (the single schema
# every surface reports through); the legacy ``fallback_stats()`` dict is
# reconstructed from it below.  Scoped tallies stay plain Counters.
_MET_KERNEL_CALLS = obs_counters.registry().counter(
    "kernels.kernel_calls", "fused kernel dispatches", labels=("op",)
)
_MET_FALLBACKS = obs_counters.registry().counter(
    "kernels.fallbacks", "jnp-reference fallbacks",
    labels=("op", "shape", "reason"),
)
_SCOPES: list[FallbackScope] = []


@contextlib.contextmanager
def fallback_scope(scope: FallbackScope | None = None):
    """Collect dispatch accounting for the duration of a ``with`` block.

    Yields a :class:`FallbackScope` whose counters see only the dispatch
    decisions made while the scope is active.  Pass an existing scope to
    re-enter it (the serving Engine accumulates one scope across its
    lifetime's call sites).  Unlike ``reset_fallback_stats()`` +
    ``fallback_stats()``, a scope neither clears nor double-reads the
    process-wide tally, and it observes decisions even when the inner jitted
    kernels were already compiled earlier in the process.
    """
    scope = FallbackScope() if scope is None else scope
    _SCOPES.append(scope)
    try:
        yield scope
    finally:
        _SCOPES.remove(scope)


def _note_kernel(op: str) -> None:
    _MET_KERNEL_CALLS.inc(1, op)
    for scope in _SCOPES:
        scope.kernel_calls[op] += 1


def _note_fallback(op: str, shape, reason: str) -> None:
    key = (op, str(tuple(shape)), reason)
    if _MET_FALLBACKS.value(*key) == 0:
        logger.warning(
            "kernels.%s: shape %s falls back to the jnp reference (%s)",
            op, tuple(shape), reason,
        )
    _MET_FALLBACKS.inc(1, *key)
    for scope in _SCOPES:
        scope.fallbacks[key] += 1


def note_fallback(op: str, shape, reason: str) -> None:
    """Public hook for callers that bypass a kernel *before* reaching its
    wrapper (e.g. lpt.dense_apply on DR rounding or a tiered hot-row
    cache).  Keeps the 'never silent' contract: every kernels-on dispatch
    that lands on the jnp path is counted."""
    _note_fallback(op, shape, reason)


def _fault_forced(op: str) -> bool:
    """True when an installed FaultPlan forces ``op`` onto the jnp reference
    path (site ``kernels.force_fallback``).  Consulted at *trace* time — the
    wrappers run inside jit, so a per-step schedule cannot apply here; the
    seam fires for every dispatch while the plan is installed, optionally
    narrowed to a subset via the spec's ``ops`` param.  Bitwise-safe by the
    kernel contract (the references are the kernels' oracles); every forced
    dispatch is counted with reason ``fault-injected``."""
    from repro.faults import plan as faultplan

    spec = faultplan.lookup("kernels.force_fallback")
    if spec is None:
        return False
    ops_sel = spec.param("ops")
    return ops_sel is None or op in ops_sel


def _stats_of(kernel_calls: collections.Counter,
              fallbacks: collections.Counter) -> dict:
    return {
        "kernel_calls": dict(kernel_calls),
        "fallbacks": [
            {"op": op, "shape": shape, "reason": reason, "count": int(c)}
            for (op, shape, reason), c in sorted(fallbacks.items())
        ],
        "total_fallbacks": int(sum(fallbacks.values())),
    }


def fallback_stats() -> dict:
    """Snapshot of kernel-vs-fallback dispatch since the last reset.

    ``kernel_calls``/``fallbacks`` count wrapper dispatches (per call when
    eager, per trace under an enclosing jit); ``total_fallbacks`` is the
    number a kernels-on benchmark config asserts to be zero.

    Backward-compatible shim: the tallies live in the ``repro.obs``
    registry (``kernels.kernel_calls`` / ``kernels.fallbacks``); this
    rebuilds the pre-registry dict schema from its cells, keys unchanged
    (pinned by tests/test_obs.py).
    """
    kc = collections.Counter(
        {op: int(c) for (op,), c in _MET_KERNEL_CALLS.cells().items()}
    )
    fb = collections.Counter(
        {key: int(c) for key, c in _MET_FALLBACKS.cells().items()}
    )
    return _stats_of(kc, fb)


def reset_fallback_stats() -> None:
    _MET_KERNEL_CALLS.reset()
    _MET_FALLBACKS.reset()


# ------------------------------------------------------------------ dispatch


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pick_block(n: int, target: int) -> int | None:
    """Largest kernel-legal block for a dimension of size ``n`` (None if the
    dimension is not sublane-aligned)."""
    if n % SUBLANE:
        return None
    if n <= target:
        return n
    for b in (target, 512, 256, 128, 64, 32, 16, 8):
        if b <= target and n % b == 0:
            return b
    return None  # unreachable: SUBLANE divides n


def _lane_block(d: int) -> int:
    """Column block: the whole row, unless a wide row splits into
    lane-dense (multiple-of-128) blocks of at most ``COL_BLOCK``."""
    if _default_interpret() or d <= COL_BLOCK:
        return d
    for b in range(COL_BLOCK, 0, -LANE):
        if d % b == 0:
            return b
    return d


def _blocks_2d(rows: int, cols: int):
    if _default_interpret():
        # Whole-array blocks off-TPU: tiling is a VMEM concern, and per-tile
        # interpretation only adds loop overhead on CPU.
        if rows % SUBLANE == 0 and cols % SUBLANE == 0:
            return rows, cols
        return None
    rb = _pick_block(rows, ROW_BLOCK)
    if rb is None or cols % SUBLANE:
        return None
    return rb, _lane_block(cols)


# Inner jitted implementations: the public wrappers stay plain Python so the
# dispatch decision (and its accounting) runs on every call / enclosing
# trace, while the arithmetic still compiles once per shape here.

_ref_dequant_gather = jax.jit(ref.dequant_gather_ref)
_ref_sr_round = jax.jit(ref.sr_round_ref, static_argnums=(3,))
_ref_dequant_matmul = jax.jit(ref.dequant_matmul_ref)
_ref_dequant_gather_packed = jax.jit(
    ref.dequant_gather_packed_ref, static_argnames=("bits", "d")
)
_ref_dequant_matmul_packed = jax.jit(
    ref.dequant_matmul_packed_ref, static_argnames=("bits", "k")
)


@functools.partial(jax.jit, static_argnames=("d_block", "interpret"))
def _dequant_gather_jit(codes, step, ids, *, d_block, interpret):
    return _dequant_gather(codes, step, ids, d_block=d_block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "d", "interpret"))
def _dequant_gather_packed_jit(packed, step, ids, *, bits, d, interpret):
    return _dequant_gather_packed(
        packed, step, ids, bits=bits, d=d, interpret=interpret
    )


@functools.partial(
    jax.jit, static_argnames=("bits", "row_block", "col_block", "interpret")
)
def _sr_round_jit(w, step, noise, bits, *, row_block, col_block, interpret):
    return _sr_round(
        w, step, noise, bits, row_block=row_block, col_block=col_block,
        interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("bits", "weight_decay", "row_block", "col_block",
                     "interpret", "has_new_step"),
)
def _lpt_update_jit(codes, step, grad, noise, lr, new_step, bits, *,
                    weight_decay, row_block, col_block, interpret,
                    has_new_step):
    return _lpt_fused_update(
        codes, step, grad, noise, lr, bits,
        new_step=new_step if has_new_step else None,
        weight_decay=weight_decay, row_block=row_block, col_block=col_block,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("bits", "weight_decay", "has_new_step")
)
def _ref_lpt_update_jit(codes, step, grad, noise, lr, new_step, bits, *,
                        weight_decay, has_new_step):
    return ref.lpt_fused_update_ref(
        codes, step, grad, noise, lr, bits,
        new_step=new_step if has_new_step else None,
        weight_decay=weight_decay,
    )


@functools.partial(
    jax.jit,
    static_argnames=("bits", "d", "weight_decay", "row_block", "interpret",
                     "has_new_step"),
)
def _lpt_update_packed_jit(packed, step, grad, noise, lr, new_step, *, bits,
                           d, weight_decay, row_block, interpret,
                           has_new_step):
    return _lpt_fused_update_packed(
        packed, step, grad, noise, lr, bits, d,
        new_step=new_step if has_new_step else None,
        weight_decay=weight_decay, row_block=row_block, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("bits", "d", "weight_decay", "has_new_step")
)
def _ref_lpt_update_packed_jit(packed, step, grad, noise, lr, new_step, *,
                               bits, d, weight_decay, has_new_step):
    return ref.lpt_fused_update_packed_ref(
        packed, step, grad, noise, lr, bits, d,
        new_step=new_step if has_new_step else None,
        weight_decay=weight_decay,
    )


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def _dequant_matmul_jit(x, codes, step, *, block_m, block_n, block_k,
                        interpret):
    return _dequant_matmul(
        x, codes, step, block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("bits", "k", "block_m", "block_n", "interpret")
)
def _dequant_matmul_packed_jit(x, packed, step, *, bits, k, block_m, block_n,
                               interpret):
    return _dequant_matmul_packed(
        x, packed, step, bits=bits, k=k, block_m=block_m, block_n=block_n,
        interpret=interpret,
    )


# ------------------------------------------------------------------- wrappers


def dequant_gather(codes, step, ids, *, use_kernel: bool = True):
    """Fused int8-row gather + de-quantize: f32 [b, d] rows for flat ids.

    ``codes`` may be a raw int8 array or a :class:`CodeStore`; a packed store
    dispatches to the packed-container kernel (packed bytes move HBM->VMEM,
    the unpack happens in VMEM) — bitwise equal to the unpacked path.  A
    :class:`~repro.storage.tiered.TieredCodes` routes: the backing gather
    keeps its kernel path, and cached rows overlay through the identical
    de-quantize formula (``codes[id] * step[id]``), so the where-merge is
    bitwise-equal to an uncached gather of the same logical table.

    The gather itself is bitwise-stable across storages; consumers that need
    the *surrounding* model computation to compile identically (the cache-on
    == cache-off training contract) fence it with
    :func:`repro.core.fence.fence_call` — an ``optimization_barrier`` here is
    not enough, XLA:CPU fuses across barriers late in its pipeline.
    """
    return _dequant_gather_impl(codes, step, ids, use_kernel=use_kernel)


def _dequant_gather_impl(codes, step, ids, *, use_kernel: bool = True):
    if isinstance(codes, TieredCodes):
        base = _dequant_gather_impl(
            codes.backing, step, ids, use_kernel=use_kernel
        )
        slot = codes.slots_for(ids)
        hot_codes = rowstore.take_rows(
            codes.hot, jnp.clip(slot, 0, codes.capacity - 1)
        )
        hot = hot_codes.astype(jnp.float32) * jnp.take(step, ids)[:, None]
        return jnp.where((slot >= 0)[:, None], hot, base)
    if isinstance(codes, CodeStore) and codes.packed:
        n, d = codes.shape
        if not use_kernel:
            return _ref_dequant_gather_packed(
                codes.data, step, ids, bits=codes.bits, d=d
            )
        if _fault_forced("dequant_gather"):
            _note_fallback("dequant_gather", (n, d), "fault-injected")
            return _ref_dequant_gather_packed(
                codes.data, step, ids, bits=codes.bits, d=d
            )
        if d % SUBLANE or (not _default_interpret() and d > COL_BLOCK):
            _note_fallback(
                "dequant_gather", (n, d),
                "dim not sublane-aligned" if d % SUBLANE
                else "dim exceeds one block",
            )
            return _ref_dequant_gather_packed(
                codes.data, step, ids, bits=codes.bits, d=d
            )
        _note_kernel("dequant_gather")
        return _dequant_gather_packed_jit(
            codes.data, step, ids, bits=codes.bits, d=d,
            interpret=_default_interpret(),
        )
    if isinstance(codes, CodeStore):
        codes = codes.data
    n, d = codes.shape
    if not use_kernel:
        return _ref_dequant_gather(codes, step, ids)
    if _fault_forced("dequant_gather"):
        _note_fallback("dequant_gather", (n, d), "fault-injected")
        return _ref_dequant_gather(codes, step, ids)
    if d % SUBLANE:
        _note_fallback("dequant_gather", (n, d), "dim not sublane-aligned")
        return _ref_dequant_gather(codes, step, ids)
    _note_kernel("dequant_gather")
    return _dequant_gather_jit(
        codes, step, ids, d_block=_lane_block(d), interpret=_default_interpret()
    )


def sr_round(w, step, noise, bits: int = 8, *, use_kernel: bool = True):
    """Fused clip + stochastic-round + int8 pack (Eq. 1/4)."""
    rows, cols = w.shape
    if not use_kernel:
        return _ref_sr_round(w, step, noise, bits)
    if _fault_forced("sr_round"):
        _note_fallback("sr_round", (rows, cols), "fault-injected")
        return _ref_sr_round(w, step, noise, bits)
    blocks = _blocks_2d(rows, cols)
    if blocks is None:
        _note_fallback("sr_round", (rows, cols), "shape not sublane-aligned")
        return _ref_sr_round(w, step, noise, bits)
    _note_kernel("sr_round")
    return _sr_round_jit(
        w, step, noise, bits, row_block=blocks[0], col_block=blocks[1],
        interpret=_default_interpret(),
    )


def lpt_update(codes, step, grad, noise, lr, bits: int, *, new_step=None,
               weight_decay: float = 0.0, use_kernel: bool = True):
    """Fused Eq. (8) write-back: dequantize -> decayed step -> SR requantize.

    ``grad`` is the formed update direction (raw gradient for SGD, the Adam /
    Adagrad direction otherwise); ``new_step`` requantizes with ALPT's
    freshly learned Delta in the same pass.

    A :class:`CodeStore` input returns a CodeStore with the same layout; a
    packed store runs the packed kernel (unpack -> identical body -> re-pack,
    all in VMEM) or its packed jnp oracle on ineligible shapes.
    """
    if isinstance(codes, CodeStore) and codes.packed:
        store = codes
        rows, cols = store.shape
        has_new_step = new_step is not None
        ns = step if new_step is None else new_step
        if not use_kernel:
            out = _ref_lpt_update_packed_jit(
                store.data, step, grad, noise, lr, ns, bits=bits, d=cols,
                weight_decay=weight_decay, has_new_step=has_new_step,
            )
            return store.with_data(out)
        if _fault_forced("lpt_update"):
            _note_fallback("lpt_update", (rows, cols), "fault-injected")
            out = _ref_lpt_update_packed_jit(
                store.data, step, grad, noise, lr, ns, bits=bits, d=cols,
                weight_decay=weight_decay, has_new_step=has_new_step,
            )
            return store.with_data(out)
        rb = rows if _default_interpret() else _pick_block(rows, ROW_BLOCK)
        if rows % SUBLANE or cols % SUBLANE or rb is None:
            _note_fallback(
                "lpt_update", (rows, cols), "shape not sublane-aligned"
            )
            out = _ref_lpt_update_packed_jit(
                store.data, step, grad, noise, lr, ns, bits=bits, d=cols,
                weight_decay=weight_decay, has_new_step=has_new_step,
            )
            return store.with_data(out)
        _note_kernel("lpt_update")
        out = _lpt_update_packed_jit(
            store.data, step, grad, noise, lr, ns, bits=bits, d=cols,
            weight_decay=weight_decay, row_block=rb,
            interpret=_default_interpret(), has_new_step=has_new_step,
        )
        return store.with_data(out)
    store = codes if isinstance(codes, CodeStore) else None
    if store is not None:
        codes = store.data
    rows, cols = codes.shape
    has_new_step = new_step is not None
    ns = step if new_step is None else new_step  # placeholder keeps jit arity
    if store is not None:
        out = lpt_update(
            codes, step, grad, noise, lr, bits, new_step=new_step,
            weight_decay=weight_decay, use_kernel=use_kernel,
        )
        return store.with_data(out)
    if not use_kernel:
        return _ref_lpt_update_jit(
            codes, step, grad, noise, lr, ns, bits,
            weight_decay=weight_decay, has_new_step=has_new_step,
        )
    if _fault_forced("lpt_update"):
        _note_fallback("lpt_update", (rows, cols), "fault-injected")
        return _ref_lpt_update_jit(
            codes, step, grad, noise, lr, ns, bits,
            weight_decay=weight_decay, has_new_step=has_new_step,
        )
    blocks = _blocks_2d(rows, cols)
    if blocks is None:
        _note_fallback("lpt_update", (rows, cols), "shape not sublane-aligned")
        return _ref_lpt_update_jit(
            codes, step, grad, noise, lr, ns, bits,
            weight_decay=weight_decay, has_new_step=has_new_step,
        )
    _note_kernel("lpt_update")
    return _lpt_update_jit(
        codes, step, grad, noise, lr, ns, bits,
        weight_decay=weight_decay, row_block=blocks[0], col_block=blocks[1],
        interpret=_default_interpret(), has_new_step=has_new_step,
    )


def dequant_matmul(
    x, codes, step, *, block_m=128, block_n=128, block_k=512, use_kernel=True
):
    """Fused de-quantize x int8-weight matmul: ``x @ (step * codes).T``.

    The serving LM head: the int8 vocab table is scaled tile-by-tile in VMEM
    immediately before the MXU contraction — the fp32 table never exists in
    HBM.  Off-TPU any geometry runs as one whole-array interpreted block; on
    TPU the (m, n, k) dims must divide the (128, 128, 512) tiles or the call
    falls back (counted) to the jnp reference.

    ``codes`` may be a :class:`CodeStore`; a packed store dispatches to the
    whole-K packed kernel (bits/8 bytes per weight off HBM).
    """
    if isinstance(codes, CodeStore) and codes.packed:
        m, k = x.shape
        n, d = codes.shape
        if not use_kernel:
            return _ref_dequant_matmul_packed(
                x, codes.data, step, bits=codes.bits, k=d
            )
        if _fault_forced("dequant_matmul"):
            _note_fallback("dequant_matmul", (m, n, k), "fault-injected")
            return _ref_dequant_matmul_packed(
                x, codes.data, step, bits=codes.bits, k=d
            )
        bm, bn = min(block_m, m), min(block_n, n)
        if m % bm or n % bn:
            if _default_interpret():
                bm, bn = m, n
            else:
                _note_fallback(
                    "dequant_matmul", (m, n, k), "blocks not divisible"
                )
                return _ref_dequant_matmul_packed(
                    x, codes.data, step, bits=codes.bits, k=d
                )
        _note_kernel("dequant_matmul")
        return _dequant_matmul_packed_jit(
            x, codes.data, step, bits=codes.bits, k=d, block_m=bm,
            block_n=bn, interpret=_default_interpret(),
        )
    if isinstance(codes, CodeStore):
        codes = codes.data
    m, k = x.shape
    n, _ = codes.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    if not use_kernel:
        return _ref_dequant_matmul(x, codes, step)
    if _fault_forced("dequant_matmul"):
        _note_fallback("dequant_matmul", (m, n, k), "fault-injected")
        return _ref_dequant_matmul(x, codes, step)
    if m % bm or n % bn or k % bk:
        if _default_interpret():
            # Whole-array blocks: tiling is a TPU bandwidth concern only.
            bm, bn, bk = m, n, k
        else:
            _note_fallback("dequant_matmul", (m, n, k), "blocks not divisible")
            return _ref_dequant_matmul(x, codes, step)
    _note_kernel("dequant_matmul")
    return _dequant_matmul_jit(
        x, codes, step, block_m=bm, block_n=bn, block_k=bk,
        interpret=_default_interpret(),
    )
