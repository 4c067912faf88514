"""End-to-end CTR training for every registered embedding method.

One trainer, one DCN/DeepFM backbone, any method in ``repro.methods`` — the
trainer never names a method.  It keys off two capability surfaces:

  float-leaf methods    : joint Adam over (embedding leaves, dense params)
  integer-table methods : the method's ``fused_row_step`` (Eq. 8 for LPT,
                          Algorithm 1 for ALPT, product-rule row updates for
                          composed tables like qr_lpt)

This mirrors the paper's experimental protocol (§4.1): Adam lr 1e-3, tenfold
decay boundaries, decoupled weight decay on embeddings, Delta lr 2e-5.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults, methods, metrics
from repro.models import ctr as ctr_models
from repro.models import embedding as emb_mod
from repro.obs.trace import tracer
from repro.optim import adam_init, adam_update
from repro.storage.tiered import HotRowCache
from repro.training.run_ahead import RunAhead


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    spec: emb_mod.EmbeddingSpec
    model: str = "dcn"  # 'dcn' | 'deepfm'
    dcn: ctr_models.DCNConfig | None = None
    deepfm: ctr_models.DeepFMConfig | None = None
    lr: float = 1e-3
    emb_weight_decay: float = 5e-8
    lr_boundaries: tuple[int, ...] = ()  # steps at which lr /= 10
    seed: int = 0
    # Gradient-sync bit width for data-parallel training
    # (repro.training.data_parallel): 32 = exact fp32, 2..8 = SR-compressed.
    dp_sync_bits: int = 32
    # Tiered storage (repro.storage): > 0 composes a device hot-row cache of
    # this many rows over every cacheable sub-table of the embedding state.
    # Training reads/writes route through the cache (dirty rows write back
    # before eviction); cache-on is bitwise-equal to cache-off.  Integer-
    # table methods only.
    cache_rows: int = 0
    # Opt-in non-finite guard (repro.faults.guards): detects NaN/Inf in the
    # step's loss or updated dense params inside the jitted step and skips
    # the update (state rolls back; step/rng advance).  Off by default so
    # the default compiled graph — and its bitwise parity contracts — is
    # untouched.  Also hosts the trainer.nonfinite / alpt.delta injection
    # seams when a FaultPlan is installed.
    guard: bool = False


class TrainState(NamedTuple):
    emb_state: Any
    dense_params: Any
    dense_opt: Any
    emb_opt: Any  # Adam state over float embedding leaves (None for int tables)
    step: jax.Array
    rng: jax.Array


class CTRTrainer:
    def __init__(self, cfg: TrainerConfig):
        self.cfg = cfg
        self.spec = cfg.spec
        self.method = methods.get(cfg.spec.method)
        if cfg.model == "dcn":
            assert cfg.dcn is not None
            self.model_cfg = cfg.dcn
            self._init_model = ctr_models.init_dcn
        else:
            assert cfg.deepfm is not None
            self.model_cfg = cfg.deepfm
            self._init_model = ctr_models.init_deepfm
        self._caches: list = []  # [(CacheSlot, HotRowCache)]
        if cfg.cache_rows:
            self._storage_slots = self.method.storage_spec(self.spec)
            if not self._storage_slots:
                raise ValueError(
                    f"cache_rows > 0 but method {self.spec.method!r} exposes "
                    "no cacheable storage slots (integer-table methods only)"
                )
        self.guard_stats = faults.GuardStats() if cfg.guard else None
        self._train_step = self._build_train_step()
        self._run_ahead = RunAhead()
        self._eval_logits = jax.jit(self._logits_fn)

    # ------------------------------------------------------------ init

    def init_state(self, key: jax.Array | None = None) -> TrainState:
        key = jax.random.PRNGKey(self.cfg.seed) if key is None else key
        k_emb, k_dense, k_rng = jax.random.split(key, 3)
        emb_state = self._install_caches(self.method.init(k_emb, self.spec))
        dense_params = self._init_model(k_dense, self.model_cfg)
        dense_opt = adam_init(dense_params)
        emb_params = self.method.trainable_params(emb_state, self.spec)
        emb_opt = adam_init(emb_params) if emb_params is not None else None
        return TrainState(
            emb_state=emb_state,
            dense_params=dense_params,
            dense_opt=dense_opt,
            emb_opt=emb_opt,
            step=jnp.zeros((), jnp.int32),
            rng=k_rng,
        )

    # ------------------------------------------------------------ cache

    def _install_caches(self, emb_state):
        """Compose a hot-row cache over each cacheable slot of the state."""
        if not self.cfg.cache_rows:
            return emb_state
        self._caches = []
        for slot in self._storage_slots:
            sub = slot.get(emb_state)
            cap = max(1, min(int(self.cfg.cache_rows), slot.rows))
            cache = HotRowCache(cap, int(sub.codes.shape[0]), name=slot.name)
            emb_state = slot.put(
                emb_state, sub._replace(codes=cache.wrap(sub.codes))
            )
            self._caches.append((slot, cache))
        return emb_state

    def _maintain_caches(self, state: "TrainState", ids) -> "TrainState":
        """Post-step cache maintenance: the policy observes the batch's ids
        (write=True — the routed sparse update put cached rows' new codes in
        the hot tier only) and applies admissions/evictions in one jitted
        transaction per slot."""
        if not self._caches:
            return state
        flat = np.asarray(ids).reshape(-1)
        emb_state = state.emb_state
        for slot, cache in self._caches:
            moves = cache.observe(slot.local_ids(flat), write=True)
            if moves is None:
                continue
            sub = slot.get(emb_state)
            emb_state = slot.put(
                emb_state, sub._replace(codes=cache.apply(sub.codes, moves))
            )
        return state._replace(emb_state=emb_state)

    def export_state(self, state: "TrainState") -> "TrainState":
        """The cache-off-equivalent state: every dirty hot row folded back
        into its backing container (bitwise-equal to an uncached run) —
        what checkpoints, serving exports, and parity tests consume.  The
        live ``state`` stays valid for continued training."""
        if not self._caches:
            return state
        emb_state = state.emb_state
        for slot, cache in self._caches:
            sub = slot.get(emb_state)
            emb_state = slot.put(
                emb_state, sub._replace(codes=cache.unwrap(sub.codes))
            )
        return state._replace(emb_state=emb_state)

    def import_state(self, state: "TrainState") -> "TrainState":
        """Re-install the hot-row caches over a restored (exported) state.

        Checkpoints hold the cache-off-equivalent containers from
        :meth:`export_state`, so a restore re-wraps them with *cold* caches
        (fresh policy state).  That is bitwise-harmless for the training
        math — cache-on == cache-off per row — so exact-resume parity of
        losses and of the exported final state survives a restart even
        though cache membership does not."""
        if not self.cfg.cache_rows:
            return state
        return state._replace(
            emb_state=self._install_caches(state.emb_state)
        )

    def cache_stats(self) -> list[dict]:
        return [cache.stats() for _, cache in self._caches]

    # ------------------------------------------------------------ lr

    def _lr_at(self, step: jax.Array) -> jax.Array:
        lr = jnp.asarray(self.cfg.lr, jnp.float32)
        for b in self.cfg.lr_boundaries:
            lr = lr * jnp.where(step >= b, 0.1, 1.0)
        return lr

    # ------------------------------------------------------------ forward

    def _logits_fn(self, emb_state, dense_params, ids, *, dropout_key=None):
        rows = self.method.lookup(emb_state, ids, self.spec)
        return self._logits_from_rows(rows, dense_params, dropout_key)

    def _logits_from_rows(self, rows, dense_params, dropout_key=None):
        return ctr_models.logits_from_rows(
            dense_params, rows, self.model_cfg, model=self.cfg.model,
            dropout_key=dropout_key,
        )

    # ------------------------------------------------------------ train step

    def _build_train_step(self):
        spec = self.spec
        method = self.method

        if not method.is_integer_table:

            @jax.jit
            def step_fn(state: TrainState, ids, labels):
                lr = self._lr_at(state.step)
                rng, kd = jax.random.split(state.rng)
                emb_params = method.trainable_params(state.emb_state, spec)

                def loss_fn(emb_params, dense_params):
                    emb_state = method.with_params(state.emb_state, emb_params, spec)
                    logits = self._logits_fn(
                        emb_state, dense_params, ids, dropout_key=kd
                    )
                    return ctr_models.bce_loss(logits, labels)

                loss, (g_emb, g_dense) = jax.value_and_grad(loss_fn, (0, 1))(
                    emb_params, state.dense_params
                )
                new_dense, dense_opt = adam_update(
                    g_dense, state.dense_opt, state.dense_params, lr
                )
                new_emb_params, emb_opt = adam_update(
                    g_emb, state.emb_opt, emb_params, lr,
                    weight_decay=self.cfg.emb_weight_decay,
                )
                emb_state = method.with_params(state.emb_state, new_emb_params, spec)
                return (
                    TrainState(emb_state, new_dense, dense_opt, emb_opt,
                               state.step + 1, rng),
                    {"loss": loss, "lr": lr},
                )

            if self.cfg.guard:
                step_fn = faults.wrap_ctr_step(step_fn)
            if method.has_host_refresh:
                return self.wrap_host_refresh(step_fn)
            return step_fn

        @jax.jit
        def step_fn(state: TrainState, ids, labels):
            lr = self._lr_at(state.step)
            rng, kd, kn = jax.random.split(state.rng, 3)

            def loss_from_rows(rows, dense_params):
                logits = self._logits_from_rows(rows, dense_params, kd)
                return ctr_models.bce_loss(logits, labels)

            def update_dense(g, opt, params):
                return adam_update(g, opt, params, lr)

            emb_state, new_dense, dense_opt, m = method.fused_row_step(
                state.emb_state, ids, spec=spec,
                loss_from_rows=loss_from_rows,
                dense_params=state.dense_params, dense_opt=state.dense_opt,
                update_dense=update_dense, lr=lr,
                weight_decay=self.cfg.emb_weight_decay, noise_key=kn,
            )
            return (
                TrainState(emb_state, new_dense, dense_opt, None,
                           state.step + 1, rng),
                {"lr": lr, **m},
            )

        if self.cfg.guard:
            return faults.wrap_ctr_step(step_fn)
        return step_fn

    # ------------------------------------------- grad/apply split (DP hooks)
    #
    # The fused step above is the paper-faithful single-device path (sparse
    # row updates for integer tables).  The data-parallel wrapper
    # (repro.training.data_parallel) needs to all-reduce gradients *between*
    # backward and update, so the same math is also exposed as a
    # (grad_fn, apply_fn) pair built on the method's *dense* formulation
    # (``dense_params`` / ``dense_update``): it is the only shape that is
    # rank-invariant — every replica sees the same gradient pytree — and the
    # dense/sparse update parity is regression-tested in tests/test_lpt_alpt.py.

    def build_grad_fn(self):
        """Per-(micro)batch backward: (state, ids, labels, kd) -> (loss, grads).

        ``grads`` is ``(g_emb, g_dense)`` where ``g_emb`` mirrors the
        method's ``dense_params`` — the trainable-params pytree for float
        methods, the dense [n, d] de-quantized-table gradient for integer
        tables.
        """
        spec = self.spec
        method = self.method

        def grad_fn(state: TrainState, ids, labels, kd):
            emb_params = method.dense_params(state.emb_state, spec)

            def loss_fn(emb_params, dense_params):
                rows = method.dense_lookup(state.emb_state, emb_params, ids, spec)
                logits = self._logits_from_rows(rows, dense_params, kd)
                return ctr_models.bce_loss(logits, labels)

            return jax.value_and_grad(loss_fn, (0, 1))(
                emb_params, state.dense_params
            )

        return grad_fn

    def build_apply_fn(self):
        """Post-sync update: consumes the (synced) gradients from
        :meth:`build_grad_fn` and returns ``(new_state, metrics)``.

        Signature: ``apply_fn(state, loss, grads, *, lr, rng, kn,
        delta_grad=None, batch_rows=None)``.  ``kn`` keys the SR write-back
        noise (int tables); ``delta_grad(w_new, step_vec, dense_params,
        gscale) -> g_step`` supplies the (synced) ALPT Delta gradient;
        ``batch_rows`` is the paper's b for the Delta gradient scale — the
        GLOBAL batch's table-row lookups, so the scale is independent of how
        the batch is sharded over replicas.
        """
        spec = self.spec
        method = self.method
        wd = self.cfg.emb_weight_decay

        def apply_fn(state, loss, grads, *, lr, rng, kn=None,
                     delta_grad=None, batch_rows=None):
            g_emb, g_dense = grads
            new_dense, dense_opt = adam_update(
                g_dense, state.dense_opt, state.dense_params, lr
            )
            wrapped = None
            if delta_grad is not None:
                # Algorithm 1 line 4 evaluates at the UPDATED dense params.
                def wrapped(w_new, step_vec, gscale):
                    return delta_grad(w_new, step_vec, new_dense, gscale)

            emb_state, emb_opt, aux = method.dense_update(
                state.emb_state, state.emb_opt, g_emb, spec=spec, lr=lr,
                weight_decay=wd, noise_key=kn, delta_grad=wrapped,
                batch_rows=batch_rows,
            )
            return (
                TrainState(emb_state, new_dense, dense_opt, emb_opt,
                           state.step + 1, rng),
                {"loss": loss, "lr": lr, **aux},
            )

        return apply_fn

    def build_delta_grad_fn(self):
        """Per-(micro)batch ALPT Delta gradient (dense formulation):
        ``(w_new, step_vec, dense_params, ids, labels, kd, gscale) -> g_step``.
        """
        spec = self.spec
        method = self.method
        wd = self.cfg.emb_weight_decay

        def delta_fn(w_new, step_vec, dense_params, ids, labels, kd, gscale):
            def loss_fn_q(table_q):
                rows = jnp.take(table_q, ids, axis=0)
                logits = self._logits_from_rows(rows, dense_params, kd)
                return ctr_models.bce_loss(logits, labels)

            return method.dense_delta_grad(
                w_new, step_vec, loss_fn_q, spec=spec, weight_decay=wd,
                gscale=gscale,
            )

        return delta_fn

    def wrap_host_refresh(self, step_fn):
        """Host-side periodic state refresh around a jitted step function
        (DeepLight mask recomputation for method='prune') — installed by the
        fused path and the DP wrapper whenever ``method.has_host_refresh``."""
        spec = self.spec
        method = self.method
        refresh = jax.jit(lambda s: method.host_refresh(s, spec))
        every = method.refresh_every(spec)

        def step_with_refresh(state, ids, labels):
            state, m = step_fn(state, ids, labels)
            step = int(state.step)
            with tracer().span("train.refresh", step=step):
                emb = method.host_sync(state.emb_state, step, spec)
                if step % every == 0:
                    emb = refresh(emb)
            return state._replace(emb_state=emb), m

        return step_with_refresh

    # Historical name, kept for callers of the pre-registry API.
    wrap_prune_mask_update = wrap_host_refresh

    # ------------------------------------------------------------ api

    def train_step(self, state: TrainState, ids: np.ndarray, labels: np.ndarray):
        # Span edges sit at the host boundaries only: the fused step is ONE
        # jitted function by design (its in-jit phases carry named scopes
        # instead), so ``train.step`` holds the host's work on the step —
        # the batch's copies and the dispatch — and the wait for the
        # previous step and the write-back phase get spans of their own.
        # Nothing here reads the device: with tracing off the spans are
        # shared null context managers, the fence is a no-op, and the host
        # runs one step ahead of the device — the jitted computation is
        # identical either way (tests/test_obs.py holds the instrumented
        # run bitwise-equal).
        tr = tracer()
        with tr.span("train.step"):
            with tr.span("train.feed"):
                ids_d, labels_d = jnp.asarray(ids), jnp.asarray(labels)
            with tr.span("train.dispatch"):
                state, m = self._train_step(state, ids_d, labels_d)
            tr.fence(m)
        with tr.span("train.wait"):
            self._run_ahead.dispatched(m)
        if self.guard_stats is not None:
            self.guard_stats.observe(m)
        with tr.span("train.writeback"):
            state = self._maintain_caches(state, ids)
        return state, m

    def evaluate(self, state: TrainState, batches) -> dict[str, float]:
        all_labels, all_probs = [], []
        for ids, labels in batches:
            logits = self._eval_logits(
                state.emb_state, state.dense_params, jnp.asarray(ids)
            )
            all_probs.append(np.asarray(jax.nn.sigmoid(logits)))
            all_labels.append(labels)
        labels = np.concatenate(all_labels)
        probs = np.concatenate(all_probs)
        return {
            "auc": metrics.auc(labels, probs),
            "logloss": metrics.logloss(labels, probs),
        }

    def fit(self, data, *, steps: int, batch_size: int, eval_every: int = 0,
            eval_batches: int = 20, log=None):
        state = self.init_state()
        history = []
        for i in range(steps):
            ids, labels = data.batch("train", i, batch_size)
            state, m = self.train_step(state, ids, labels)
            if eval_every and (i + 1) % eval_every == 0:
                ev = self.evaluate(
                    state, data.batches("valid", batch_size, eval_batches)
                )
                history.append({"step": i + 1, **ev, "loss": float(m["loss"])})
                if log:
                    log(history[-1])
        return state, history
