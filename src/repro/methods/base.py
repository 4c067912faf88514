"""The `EmbeddingMethod` protocol + registry.

The paper's thesis is that the embedding *method* (fp32, LPT, ALPT, QAT,
hashing, pruning, ...) is the swappable axis of a CTR/LM system.  This module
makes that axis a first-class object: every method is a registered
:class:`EmbeddingMethod` instance, and every consumer — both trainers, the
data-parallel wrapper, sharding specs, serving, checkpointing, launchers —
dispatches through :func:`get` instead of string chains.

A method bundles three things:

* **state** — ``init`` / ``lookup`` / ``memory_bytes`` / ``serving_table`` /
  ``checkpoint_schema`` / ``table_pspec`` sharding hints;
* **float-leaf training** — ``trainable_params`` / ``with_params`` expose the
  differentiable leaves for methods whose table is ordinary float state
  (fp, lsq, pact, hash, prune); the trainers run a generic joint-Adam step;
* **integer-table training** — methods whose table is integer codes
  (lpt, alpt, qr_lpt) instead implement the row/sparse formulation
  (``fused_row_step`` / ``sparse_apply``: paper Eq. 8 / Algorithm 1) and the
  dense formulation (``dense_params`` / ``dense_update``: rank-invariant
  [n, d] gradients for the data-parallel and pjit paths).

Capability flags (``is_integer_table``, ``has_learned_step``,
``has_host_refresh``) replace the old ``FLOAT_METHODS``/``INT_METHODS``
tuple-membership checks everywhere.

Adding a method touches exactly one new file: subclass, decorate with
``@register("name")``, import it from ``repro/methods/__init__.py`` (or any
plugin module).  ``repro/methods/qr_lpt.py`` is the worked example — a
composed method (QR hashing over int8 LPT tables) the old two-bucket split
could not express, registered without touching any trainer.
"""
from __future__ import annotations

import abc
import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import alpt as alpt_core
from repro.core import fence
from repro.core import pruning as pruning_core
from repro.dist.context import hint
from repro.kernels import ops as kernel_ops
from repro.optim import adam_update
from repro.serving import table as serving_tbl
from repro.storage import base as rowstore


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@dataclasses.dataclass(frozen=True)
class EmbeddingSpec:
    """Declarative description of one embedding table (method + geometry).

    ``method`` names a registered :class:`EmbeddingMethod`; the remaining
    fields are the union of every method's hyper-parameters (each method
    reads only the ones it understands).
    """

    method: str  # any name in repro.methods.available()
    n: int
    d: int
    bits: int = 8
    init_scale: float = 1e-2
    # LPT (Xu et al. 2021) fixes Delta via a tuned clip value:
    clip_value: float | None = None
    # ALPT hyper-parameters (paper §4.1):
    alpt: alpt_core.ALPTConfig = alpt_core.ALPTConfig()
    row_optimizer: str = "adam"
    hash_compression: float = 2.0
    prune: pruning_core.PruneConfig = pruning_core.PruneConfig()
    # Route integer-table lookups and dense write-backs through the Pallas
    # kernel suite (repro.kernels.ops); the sparse step is XLA's row path
    # either way.  Default on; the
    # wrappers auto-interpret off-TPU and fall back — counted, never silently
    # — on kernel-ineligible shapes.
    use_kernels: bool = True
    # Pad the table geometry up to kernel tiles at init: rows round up to the
    # sublane multiple *past* the id space (one scratch row that nothing
    # writes), dim rounds up to the sublane multiple.  Lookups/dense tables
    # are sliced back to (n, d), so padding is invisible to the model — it
    # exists so real geometries hit the kernel path instead of the shape
    # fallback.
    pad_to_tiles: bool = False
    # Code-container layout (repro.core.codestore): True packs sub-byte code
    # widths (bits in {2, 4}) into uint8 at 8//bits codes per byte; False
    # keeps one byte per code.  Pure storage choice — training and serving
    # are bitwise identical either way (the packed-parity test bar).
    packed: bool = True
    # Per-field composition (the 'mixed' method): cardinalities of the CTR
    # fields this table spans (sum == n), and optionally an explicit bit
    # width per field.  None leaves the table a single group at `bits`.
    field_cards: tuple[int, ...] | None = None
    field_bits: tuple[int, ...] | None = None

    @property
    def is_integer_table(self) -> bool:
        return get(self.method).is_integer_table

    @property
    def n_padded(self) -> int:
        """Allocated rows: id space (+ scratch row, sublane-rounded) if padded."""
        if not self.pad_to_tiles:
            return self.n
        return _round_up(self.n + 1, kernel_ops.SUBLANE)

    @property
    def d_padded(self) -> int:
        """Allocated embedding width (sublane-rounded if padded)."""
        if not self.pad_to_tiles:
            return self.d
        return _round_up(self.d, kernel_ops.SUBLANE)


class EmbeddingMethod(abc.ABC):
    """One embedding method: state, lookup, training formulations, metadata.

    Defaults implement the float-leaf family generically; integer-table
    methods subclass :class:`IntegerTableMethod` instead.
    """

    name: str = "?"  # set by @register

    # ---------------------------------------------------------- capabilities
    #: Table is integer codes (no differentiable float leaves); trainers use
    #: the row/sparse + dense formulations instead of joint Adam.
    is_integer_table: bool = False
    #: Method learns its step size Delta via a second fake-quant forward
    #: (ALPT Algorithm 1 line 4); trainers must supply a delta-grad closure.
    has_learned_step: bool = False
    #: Method needs a host-side state refresh between steps (DeepLight mask
    #: recomputation); trainers wrap the jitted step with ``host_refresh``.
    has_host_refresh: bool = False

    def capabilities(self) -> dict[str, bool]:
        return {
            "is_integer_table": self.is_integer_table,
            "has_learned_step": self.has_learned_step,
            "has_host_refresh": self.has_host_refresh,
        }

    # ---------------------------------------------------------------- state

    @abc.abstractmethod
    def init(self, key: jax.Array, spec: EmbeddingSpec) -> Any:
        """Initialize the table state pytree."""

    @abc.abstractmethod
    def lookup(self, state: Any, ids: jax.Array, spec: EmbeddingSpec,
               grad_scale: float = 1.0) -> jax.Array:
        """De-quantized / fake-quantized / masked rows [..., d]."""

    @abc.abstractmethod
    def memory_bytes(self, state: Any, spec: EmbeddingSpec, *,
                     training: bool) -> int:
        """Embedding-memory accounting (paper Table 1 compression columns).

        Storage-actual: integer-table methods report their container's
        resident bytes (``repro.storage.base.resident_bytes_of`` — packed
        sub-byte widths count ceil(d*bits/8) per row, not one byte per
        code)."""

    # ------------------------------------------------- float-leaf formulation

    @abc.abstractmethod
    def trainable_params(self, state: Any, spec: EmbeddingSpec) -> Any:
        """Differentiable leaves (None for integer tables)."""

    @abc.abstractmethod
    def with_params(self, state: Any, params: Any, spec: EmbeddingSpec) -> Any:
        """Rebuild state from updated differentiable leaves."""

    # ------------------------------------------------------ dense formulation
    #
    # The shape every distributed consumer wants: a differentiable pytree
    # whose gradient is identical on every replica.  Float-leaf methods use
    # their trainable params; integer tables use the de-quantized [n, d]
    # table (the only rank-invariant shape — see training/data_parallel.py).

    def dense_params(self, state: Any, spec: EmbeddingSpec) -> Any:
        """The pytree the dense/DP backward differentiates w.r.t."""
        return self.trainable_params(state, spec)

    def dense_lookup(self, state: Any, params: Any, ids: jax.Array,
                     spec: EmbeddingSpec) -> jax.Array:
        """Rows for ``ids``, differentiable in ``params``."""
        return self.lookup(self.with_params(state, params, spec), ids, spec)

    def dense_table_from(self, state: Any, params: Any,
                         spec: EmbeddingSpec) -> jax.Array:
        """Full [n, d] float table, differentiable in ``params`` (LM path)."""
        return self.dense_lookup(state, params, jnp.arange(spec.n), spec)

    def hint_dense_params(self, params: Any) -> Any:
        """Sharding hint for the dense params / their gradient (identity by
        default; [n, d]-table-shaped methods constrain to 'embed_table')."""
        return params

    def dense_update(self, state: Any, opt: Any, grads: Any, *,
                     spec: EmbeddingSpec, lr: jax.Array, weight_decay: float,
                     noise_key: jax.Array | None = None,
                     delta_grad: Callable | None = None,
                     batch_rows: int | None = None):
        """Consume (synced) dense-formulation gradients.

        Returns ``(new_state, new_opt, aux_metrics)``.  The default is the
        float-leaf rule: Adam over ``trainable_params`` with decoupled weight
        decay (``opt`` is the caller-held Adam state over those leaves).
        ``delta_grad(w_new, step_vec, gscale) -> g_step`` supplies the synced
        ALPT Delta gradient; ``batch_rows`` is the paper's b (global batch's
        table-row lookups) — both ignored unless ``has_learned_step``.
        """
        params = self.trainable_params(state, spec)
        new_params, new_opt = adam_update(
            grads, opt, params, lr, weight_decay=weight_decay
        )
        return self.with_params(state, new_params, spec), new_opt, {}

    # -------------------------------------------------- row/sparse (fused)

    def fused_row_step(self, state: Any, ids: jax.Array, *,
                       spec: EmbeddingSpec, loss_from_rows: Callable,
                       dense_params: Any, dense_opt: Any,
                       update_dense: Callable, lr: jax.Array,
                       weight_decay: float, noise_key: jax.Array):
        """Single-device fused train step (integer-table methods only).

        ``loss_from_rows(rows, dense_params) -> scalar`` closes over the
        batch; ``update_dense(g, opt, params) -> (new_params, new_opt)`` is
        the caller's dense-parameter optimizer.  Returns
        ``(new_state, new_dense_params, new_dense_opt, metrics)``.
        """
        raise NotImplementedError(
            f"{self.name!r} has no row formulation; use the float-leaf path"
        )

    def dense_delta_grad(self, w_new, step_vec, loss_fn_q, *,
                         spec: EmbeddingSpec, weight_decay: float,
                         gscale: float) -> jax.Array:
        raise NotImplementedError(f"{self.name!r} has no learned step size")

    # ---------------------------------------------------- host-side refresh

    def host_sync(self, state: Any, step: int, spec: EmbeddingSpec) -> Any:
        """Cheap host-side per-step state sync (e.g. schedule clocks)."""
        return state

    def host_refresh(self, state: Any, spec: EmbeddingSpec) -> Any:
        """Jittable periodic refresh (e.g. DeepLight mask recomputation)."""
        raise NotImplementedError(f"{self.name!r} has no host refresh")

    def refresh_every(self, spec: EmbeddingSpec) -> int:
        raise NotImplementedError(f"{self.name!r} has no host refresh")

    # ------------------------------------------------------- serving / eval

    def eval_table(self, state: Any, spec: EmbeddingSpec) -> jax.Array:
        """The [n, d] table evaluation forwards read (training semantics)."""
        return self.dense_table_from(state, self.dense_params(state, spec), spec)

    def serving_table(self, state: Any, spec: EmbeddingSpec) -> jax.Array:
        """The [n, d] table a serving process ships (post-training export)."""
        return self.eval_table(state, spec)

    def serving_state(self, state: Any, spec: EmbeddingSpec):
        """What a serving Engine keeps *resident* (repro.serving).

        Integer-table methods return their codes + scales
        (:class:`repro.serving.table.QuantTable` — the fp32 table is never
        materialized); the float-leaf default wraps the fp export.  Optimizer
        slots (Adam moments, masks' training state) are always dropped here:
        serving residency is inference state only.
        """
        return serving_tbl.FloatTable(self.serving_table(state, spec))

    def storage_spec(self, spec: EmbeddingSpec) -> tuple:
        """Cacheable sub-tables of the training state (the tiered hot-row
        cache hook, :mod:`repro.storage`).

        Returns a tuple of :class:`repro.storage.base.CacheSlot`, one per
        int-code table inside the state: ``get``/``put`` project the slot's
        ``LPTTable`` out of / back into the state, ``local_ids`` maps global
        feature ids to the slot's local row space (non-members -> -1).
        Float-leaf methods have nothing to cache -> ``()``.
        """
        return ()

    # -------------------------------------------------- sharding / metadata

    def table_pspec(self, row, col, *, row_optimizer: str = "adam"):
        """PartitionSpec pytree mirroring the state; ``row``/``col`` are the
        mesh-axis entries chosen (divisibility-guarded) by the caller."""
        return P(row, col)

    def param_pspec(self, row, col):
        """PartitionSpec pytree mirroring ``trainable_params`` (None for
        integer tables — they carry no float-leaf optimizer state)."""
        return P(row, col)

    def checkpoint_schema(self, spec: EmbeddingSpec) -> dict:
        """Leaf path -> {shape, dtype} of the state pytree, for checkpoint
        manifests (int8 codes must survive save/restore as int8)."""
        sds = jax.eval_shape(
            functools.partial(self.init, spec=spec),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        flat, _ = jax.tree_util.tree_flatten_with_path(sds)
        return {
            jax.tree_util.keystr(path): {
                "shape": [int(s) for s in leaf.shape],
                "dtype": str(leaf.dtype),
            }
            for path, leaf in flat
        }


class IntegerTableMethod(EmbeddingMethod):
    """Base for methods whose table is integer state (no float leaves).

    Subclasses supply ``dense_table`` (de-quantize everything), a paper-Eq.-8
    style ``sparse_apply``/``dense_update`` pair, and inherit a generic fused
    row step: joint backward w.r.t. (looked-up rows, dense params), then the
    sparse row update.
    """

    is_integer_table = True

    def trainable_params(self, state, spec):
        return None

    def with_params(self, state, params, spec):
        return state

    @abc.abstractmethod
    def dense_table(self, state: Any, spec: EmbeddingSpec) -> jax.Array:
        """Materialize the full de-quantized [n, d] table."""

    @abc.abstractmethod
    def sparse_apply(self, state: Any, ids: jax.Array, g_rows: jax.Array, *,
                     spec: EmbeddingSpec, lr: jax.Array, weight_decay: float,
                     noise_key: jax.Array) -> Any:
        """Row update from per-occurrence cotangents (paper Eq. 8)."""

    def dense_params(self, state, spec):
        return self.dense_table(state, spec)

    def dense_lookup(self, state, params, ids, spec):
        """Rows for ``ids``, differentiable in the dense [n, d] ``params``.

        Kernels-on, the *forward* reads the int8 codes through the fused
        ``ops.dequant_gather`` (1 byte/elem instead of gathering the
        materialized fp32 table), while the *backward* stays the exact
        transpose of ``jnp.take`` — ``params`` always equals the de-quantized
        table at call time, so the two forwards are bitwise identical and
        autodiff sees the same function either way.
        """
        if not spec.use_kernels:
            return jnp.take(params, ids, axis=0)
        method = self

        @jax.custom_vjp
        def kernel_gather(p):
            return method.lookup(state, ids, spec)

        def fwd(p):
            return kernel_gather(p), p

        def bwd(p, g):
            _, pull = jax.vjp(lambda q: jnp.take(q, ids, axis=0), p)
            return pull(g)

        kernel_gather.defvjp(fwd, bwd)
        return kernel_gather(params)

    def dense_table_from(self, state, params, spec):
        return params

    def hint_dense_params(self, params):
        return hint(params, "embed_table")

    def serving_table(self, state, spec):
        """Serving export: de-quantize through the fused gather kernel, so
        the fp32 table first exists in the serving process's output buffer —
        the int8 codes are the only table read from HBM (bitwise-identical
        to the jnp export)."""
        if not spec.use_kernels:
            return self.eval_table(state, spec)
        return self.lookup(state, jnp.arange(spec.n), spec)

    def serving_state(self, state, spec):
        """int8-resident serving export: the codes + per-row Delta as-is.

        No de-quantization happens here at all — the Engine's jitted steps
        read rows through ``ops.dequant_gather`` and the tied LM head through
        ``ops.dequant_matmul``, so the fp32 table is deleted from the serving
        story entirely (the PR-5 redesign).  Works for any state whose table
        is a single ``LPTTable`` (lpt, alpt); composed tables override.
        """
        return serving_tbl.QuantTable(
            codes=state.codes, step=state.step, n=spec.n, d=spec.d,
            use_kernels=spec.use_kernels,
        )

    def storage_spec(self, spec):
        """Single-table identity slot — works for any state that *is* one
        ``LPTTable`` (lpt, alpt).  Composed methods override."""
        return (rowstore.CacheSlot(
            name="table", rows=spec.n,
            get=lambda s: s,
            put=lambda s, t: t,
            local_ids=lambda ids: np.asarray(ids),
        ),)

    def fused_row_step(self, state, ids, *, spec, loss_from_rows, dense_params,
                       dense_opt, update_dense, lr, weight_decay, noise_key):
        rows0 = self.lookup(state, ids, spec)
        # Fence the model forward/backward so it compiles identically whatever
        # storage backs the codes (plain, packed, tiered) — the cache-on ==
        # cache-off bitwise contract.  Feature ids are non-negative, so any
        # id doubles as the fence's runtime tick.
        loss, (g_rows, g_dense) = fence.fence_call(
            jax.value_and_grad(loss_from_rows, (0, 1)),
            (rows0, dense_params),
            tick=ids.reshape(-1)[0],
        )
        new_dense, new_opt = update_dense(g_dense, dense_opt, dense_params)
        new_state = self.sparse_apply(
            state, ids, g_rows, spec=spec, lr=lr, weight_decay=weight_decay,
            noise_key=noise_key,
        )
        return new_state, new_dense, new_opt, {"loss": loss}

    def param_pspec(self, row, col):
        return None


# ------------------------------------------------------------------ registry

_REGISTRY: dict[str, EmbeddingMethod] = {}


def register(name: str):
    """Class decorator: instantiate and register under ``name``."""

    def deco(cls):
        if name in _REGISTRY:
            raise ValueError(f"embedding method {name!r} already registered")
        cls.name = name
        _REGISTRY[name] = cls()
        return cls

    return deco


def get(name: str) -> EmbeddingMethod:
    """The registered method instance for ``name`` (ValueError if unknown)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown embedding method {name!r}; registered: {available()}"
        ) from None


def available() -> tuple[str, ...]:
    """Sorted names of every registered method."""
    return tuple(sorted(_REGISTRY))
