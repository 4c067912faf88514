"""Jaxpr-level invariant checkers.

Source scanning cannot see what XLA will actually materialize; these
checkers trace the *real* jitted steps (both trainers, both Engines, the
compressed collectives) with :func:`jax.make_jaxpr` and walk every
equation — recursing into ``pjit``/``while``/``cond``/``scan``/
``shard_map`` sub-jaxprs — asserting the contracts the runtime parity
tests hold numerically:

* :func:`check_no_f32_table` — the int8-resident serving contract: no
  float intermediate of any full-table ``[vocab, dim]`` geometry.
* :func:`check_codes_reach_float_via_dequant` — every int8→float widen is
  a dequant (its product feeds a scale multiply); a uint8→float widen is
  categorically wrong (packed bytes are not codes).
* :func:`check_packed_stays_packed` — packed sub-byte tables never
  round-trip through a full-table logical-int8 intermediate outside the
  container (per-row unpacks are the contract; whole-table unpacks are
  the leak).
* :func:`check_wire_stays_packed` — collective payloads at sync_bits<=4
  cross the wire as packed uint8, never as widened logical codes.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterator

import jax
from jax.extend import core as jcore

from repro.analysis.findings import Finding

__all__ = [
    "walk_eqns",
    "check_no_f32_table",
    "check_codes_reach_float_via_dequant",
    "check_packed_stays_packed",
    "check_wire_stays_packed",
    "CHECKS",
]


def _subjaxprs(eqn) -> Iterator[jcore.Jaxpr]:
    for val in eqn.params.values():
        stack = [val]
        while stack:
            v = stack.pop()
            if isinstance(v, jcore.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jcore.Jaxpr):
                yield v
            elif isinstance(v, (tuple, list)):
                stack.extend(v)


def walk_eqns(jaxpr) -> Iterator:
    """Every eqn in ``jaxpr`` and all nested sub-jaxprs, depth-first."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from walk_eqns(sub)


def _aval(var):
    return getattr(var, "aval", None)


def _shape_dtype(var):
    aval = _aval(var)
    if aval is None or not hasattr(aval, "shape"):
        return None, None
    return tuple(aval.shape), getattr(aval, "dtype", None)


def trace(fn: Callable, *args, **kwargs) -> jcore.ClosedJaxpr:
    return jax.make_jaxpr(fn)(*args, **kwargs)


# --------------------------------------------------------------------------
# checker 1: int8-resident serving — no f32 full-table intermediate
# --------------------------------------------------------------------------

def check_no_f32_table(closed, forbidden_shapes, target: str
                       ) -> list[Finding]:
    """No float32/float16/bfloat16 intermediate of a full-table shape.

    ``forbidden_shapes`` is the set of table geometries for the traced
    spec: the logical ``(n, d)``, the padded ``(n_padded, d_padded)``, and
    each sub-table's allocation for composed (qr/mixed) methods.
    """
    import numpy as np
    forbidden = {tuple(s) for s in forbidden_shapes}
    out = []
    seen = set()
    for eqn in walk_eqns(closed):
        for var in eqn.outvars:
            shape, dtype = _shape_dtype(var)
            if shape is None or shape not in forbidden:
                continue
            if dtype is None or not np.issubdtype(dtype, np.floating):
                continue
            key = (shape, str(dtype), eqn.primitive.name)
            if key in seen:
                continue
            seen.add(key)
            out.append(Finding(
                rule="jaxpr-no-f32-table", path=f"<target:{target}>", line=0,
                message=f"`{eqn.primitive.name}` materializes a {dtype} "
                f"intermediate of full-table shape {shape}",
                hint="the Engine is int8-resident: gather rows first, "
                "dequantize per-row (ops.dequant_gather), never the table",
            ))
    return out


# --------------------------------------------------------------------------
# checker 2: codes reach float only through dequant
# --------------------------------------------------------------------------

_PASS_THROUGH = {
    "reshape", "broadcast_in_dim", "transpose", "squeeze", "slice",
    "dynamic_slice", "gather", "expand_dims", "copy", "convert_element_type",
    "stop_gradient", "optimization_barrier",
}


def check_codes_reach_float_via_dequant(closed, target: str
                                        ) -> list[Finding]:
    """Every int8→float convert feeds a scale multiply (a dequant).

    Dequantization is ``codes * step`` — so the float image of a code
    array must (possibly through shape-only ops) be consumed by ``mul``.
    An int8→float convert whose result reaches anything else widened raw
    codes without a scale: exactly the silent-dequant bug class.  uint8
    (packed bytes) must never convert to float at all.
    """
    import numpy as np
    out: list[Finding] = []
    # var -> producing eqn, and var -> consuming eqns
    consumers: dict = {}
    for eqn in walk_eqns(closed):
        for var in eqn.invars:
            if not isinstance(var, jcore.Literal):
                consumers.setdefault(var, []).append(eqn)
    for eqn in walk_eqns(closed):
        if eqn.primitive.name != "convert_element_type":
            continue
        (shape, src_dtype) = _shape_dtype(eqn.invars[0])
        (_, dst_dtype) = _shape_dtype(eqn.outvars[0])
        if src_dtype is None or dst_dtype is None:
            continue
        if not np.issubdtype(dst_dtype, np.floating):
            continue
        if src_dtype == np.uint8:
            out.append(Finding(
                rule="jaxpr-codes-dequant-only", path=f"<target:{target}>",
                line=0,
                message=f"packed uint8 bytes of shape {shape} converted "
                f"directly to {dst_dtype}",
                hint="packed bytes are containers, not codes: unpack to "
                "logical int8 inside CodeStore/kernels, then dequant",
            ))
            continue
        if src_dtype != np.int8:
            continue
        if not _feeds_mul(eqn.outvars[0], consumers):
            out.append(Finding(
                rule="jaxpr-codes-dequant-only", path=f"<target:{target}>",
                line=0,
                message=f"int8 codes of shape {shape} widened to "
                f"{dst_dtype} without a scale multiply (raw dequant-less "
                "widen)",
                hint="float images of codes must be `codes * step` — "
                "route through ops.dequant_gather / quant dequantize",
            ))
    return out


def _feeds_mul(var, consumers, depth: int = 0) -> bool:
    if depth > 8:
        return True  # deep chains: give the benefit of the doubt
    eqns = consumers.get(var, [])
    if not eqns:
        # unused inside this (sub)jaxpr: it is an output threaded onward —
        # cross-jaxpr dataflow is out of scope, assume the consumer scales.
        return True
    for eqn in eqns:
        name = eqn.primitive.name
        if name in ("mul", "div", "dot_general", "integer_pow"):
            continue
        if name in _PASS_THROUGH or name.startswith(("pjit", "custom_")):
            if name in _PASS_THROUGH and eqn.outvars:
                if all(_feeds_mul(o, consumers, depth + 1)
                       for o in eqn.outvars):
                    continue
            else:
                continue
            return False
        if name in ("while", "scan", "cond"):
            continue  # loop-carried: checked inside the sub-jaxpr walk
        return False
    return True


# --------------------------------------------------------------------------
# checker 3: packed leaves never round-trip through full-table int8
# --------------------------------------------------------------------------

def check_packed_stays_packed(closed, forbidden_shapes, target: str
                              ) -> list[Finding]:
    """No full-table logical-int8 intermediate when the store is packed.

    Packed sub-byte tables unpack *rows* at the point of use (in-VMEM for
    kernels, per-gather for the reference paths).  A whole-table int8
    intermediate is the container leaking: 2x-4x the resident bytes the
    packing bought, in the middle of a jitted step.
    """
    import numpy as np
    forbidden = {tuple(s) for s in forbidden_shapes}
    out = []
    seen = set()
    for eqn in walk_eqns(closed):
        for var in eqn.outvars:
            shape, dtype = _shape_dtype(var)
            if shape is None or shape not in forbidden:
                continue
            if dtype != np.int8:
                continue
            key = (shape, eqn.primitive.name)
            if key in seen:
                continue
            seen.add(key)
            out.append(Finding(
                rule="jaxpr-packed-containment", path=f"<target:{target}>",
                line=0,
                message=f"`{eqn.primitive.name}` materializes a full-table "
                f"logical int8 intermediate {shape} from a packed store",
                hint="unpack rows at the point of use (take_rows / in-VMEM "
                "kernel unpack), never the whole container",
            ))
    return out


# --------------------------------------------------------------------------
# checker 4: collective wire stays packed at sync_bits<=4
# --------------------------------------------------------------------------

_COLLECTIVES = {
    "psum", "all_gather", "all_to_all", "ppermute", "reduce_scatter",
    "psum_scatter", "all_reduce",
}


def check_wire_stays_packed(closed, target: str, *,
                            min_payload: int = 2) -> list[Finding]:
    """Every non-scalar collective payload is uint8 (the packed wire).

    At sync_bits<=4 the compressed all-reduce ships packed bytes and sums
    after unpack; a widened (int32/f32) payload of more than
    ``min_payload`` elements is the wire silently un-compressing.  Scalar
    reductions (the shared absmax pmax) are exempt.
    """
    import math
    import numpy as np
    out = []
    seen = set()
    for eqn in walk_eqns(closed):
        if eqn.primitive.name not in _COLLECTIVES:
            continue
        for var in eqn.invars:
            shape, dtype = _shape_dtype(var)
            if shape is None or dtype is None:
                continue
            if math.prod(shape) < min_payload:
                continue  # scalar absmax / step share
            if dtype == np.uint8:
                continue
            key = (eqn.primitive.name, shape, str(dtype))
            if key in seen:
                continue
            seen.add(key)
            out.append(Finding(
                rule="jaxpr-packed-wire", path=f"<target:{target}>", line=0,
                message=f"collective `{eqn.primitive.name}` ships a "
                f"{dtype} payload of shape {shape} at packable sync_bits",
                hint="pack codes to the uint8 wire before the collective "
                "(dist.collectives._packed_psum_codes)",
            ))
    return out


CHECKS = {
    "no-f32-table": check_no_f32_table,
    "codes-dequant-only": check_codes_reach_float_via_dequant,
    "packed-containment": check_packed_stays_packed,
    "packed-wire": check_wire_stays_packed,
}
