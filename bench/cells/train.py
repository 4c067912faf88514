"""Runs training cells (traffic ``kind: "train"``).

Set-up builds one trainer and its state from the seed, makes a pool of
``pool_batches`` batches from the seed, and drives the program's own step
through the pool's first ``check_steps`` batches: those steps compile the
step and give the readings that the reference is held to.  The same state
then runs the window, cycling the pool; each step copies its batch to the
device inside the window.  ``train_samples_per_s`` is every sample trained
in the window over the window, which ends when the last step's state is
ready.

With ``"chips": 4`` in the cell the step is the program's data-parallel step
over a 4-device ``data`` mesh (``sync_bits`` from the traffic), the batch
sharded over it; ``batch`` is then per chip.

``check_program`` and ``rehearsal`` serve the benchmark's tests: the first
raises where the program differs from a configuration file, the second cuts
a cell to a size the CPU runs (``tests/rehearsal/<config>.json``).
"""
from __future__ import annotations

import copy
import time

import jax
import jax.numpy as jnp
import numpy as np

import compare
import program
import reference
import zipf

NOISE_SALT = 0xBE4C


def check_program(cfg: dict) -> None:
    """Raises where the program built from ``cfg`` differs from the file."""
    program.trainer_config(cfg)


def rehearsal(cfg: dict, traffic: dict, limits: dict, cut: dict):
    """(config, traffic, limits) cut to a CPU size by the numbers in ``cut``:
    the id total scaled by ``id_scale`` (and with it the field cardinalities
    and the program's table), widths and depths as they are, the small batch
    and pool, and the limits that hold at that batch.  A data-parallel cell
    (its traffic has ``sync_bits``) takes ``cut["data_parallel"]``'s batch
    per chip and limits.  ``limits``, the real cell's, are replaced."""
    cfg = copy.deepcopy(cfg)
    scale, data = cut["id_scale"], cfg["data"]
    cfg["program"]["scale"] = scale
    data["id_total"] = int(data["id_total"] * scale)
    data["n_ids"] = sum(zipf.field_cards(data["fields"], data["id_total"], data["card_seed"]))
    part = cut["data_parallel"] if "sync_bits" in traffic else cut
    traffic = dict(traffic, batch=part["batch"], pool_batches=cut["pool_batches"],
                   check_steps=cut["check_steps"])
    return cfg, traffic, dict(part["limits"])


def make_pool(cfg: dict, traffic: dict, seed: int, chips: int):
    """(ids [pool, B, F] int32, labels [pool, B] f32, unique ids per batch)."""
    b = traffic["batch"] * chips
    n_pool = traffic["pool_batches"]
    sampler = zipf.ZipfFields.from_config(cfg["data"])
    rng = zipf.rng_for(seed, 0)
    ids = sampler.sample(rng, n_pool * b).reshape(n_pool, b, -1)
    labels = zipf.labels(rng, n_pool * b, cfg["data"]["label_p"]).reshape(n_pool, b)
    uniq = np.array([np.unique(ids[i]).size for i in range(n_pool)], np.int64)
    return ids, labels, uniq


class Program:
    """The program's step for this cell: single chip or data parallel."""

    def __init__(self, cfg: dict, traffic: dict, devices, chips: int, seed: int):
        self.trainer = program.trainer(cfg)
        state = program.init_state(self.trainer, reference.run_key(seed))
        if chips == 1:
            self.state = state
            self.step = self.trainer.train_step
            self.put = lambda ids, labels: (ids, labels)
            return
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.asarray(devices[:chips]).reshape(chips, 1), ("data", "model"))
        self.state = jax.device_put(state, NamedSharding(mesh, P()))
        self.step = program.dp_step(self.trainer, mesh, traffic["sync_bits"])
        rows = NamedSharding(mesh, P("data"))
        self.put = lambda ids, labels: (jax.device_put(ids, rows), jax.device_put(labels, rows))

    def __call__(self, ids, labels):
        self.state, m = self.step(self.state, *self.put(ids, labels))
        return m


def program_readings(prog: Program, cfg: dict, ids, labels, steps: int) -> dict:
    """The program's first ``steps`` steps through its own call and feed."""
    n, d = cfg["data"]["n_ids"], cfg["embedding"]["d"]
    before = jax.tree.map(jnp.copy, program.params(prog.state, n, d))
    losses, grad = [], None
    for i in range(steps):
        losses.append(prog(ids[i], labels[i])["loss"])
        if i == 0:
            grad = compare.norms(program.first_grads(prog.state, n, d,
                                                     cfg["optimizer"]["b1"]))
    change = compare.norms(compare.diff(program.params(prog.state, n, d), before))
    return {"loss": [float(x) for x in losses], "grad": grad, "change": change}


def reference_readings(cfg: dict, seed: int, ids, labels, steps: int, *,
                       dtype=jnp.float32, shards: int = 1, sync_bits=None,
                       keep: float = 1.0) -> dict:
    """The reference's readings over the same batches.  ``keep`` < 1 trains on
    that leading share of each batch only (a planted fault)."""
    key = reference.run_key(seed)
    noise = jax.random.fold_in(key, NOISE_SALT)
    rows = int(ids.shape[1] * keep)

    def dense_tree(grads):
        return dict(program.named_leaves(grads["dense"]))

    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda st, i, lab, k: reference.train_step(st, i, lab, cfg, k, shards,
                                                                  sync_bits))
        st = reference.init_train(key, cfg, dtype)
        p0 = _ref_params(st)
        losses = []
        for t in range(steps):
            st, loss, grads = step(st, jnp.asarray(ids[t][:rows]), jnp.asarray(labels[t][:rows]),
                                   jax.random.fold_in(noise, t))
            losses.append(loss)
            if t == 0:
                g = dense_tree(grads)
                g["table"] = grads["table"]
                grad = compare.norms(g)
                grad_all = dict(grad, step=compare.norms({"s": grads["step"]})["s"])
        change = compare.norms(compare.diff(_ref_params(st), p0))
    return {"loss": [float(x) for x in losses], "grad": grad, "change": change,
            "grad_all": grad_all}


def _ref_params(st) -> dict:
    out = dict(program.named_leaves(st.dense))
    out["table"] = st.codes * st.step[:, None]
    out["step"] = st.step
    return {k: v.astype(jnp.float32) for k, v in out.items()}


def run(r):
    cfg, traffic = r.config, r.traffic
    chips = r.cell.workload["chips"]
    steps = traffic["check_steps"]
    r.mark("imports_backend")
    ids, labels, uniq = make_pool(cfg, traffic, r.seed, chips)
    r.mark("pool")
    prog = Program(cfg, traffic, r.devices, chips, r.seed)
    jax.block_until_ready(prog.state)
    r.mark("init_state")
    readings = program_readings(prog, cfg, ids, labels, steps)
    r.setup_done()

    n_pool, batch = ids.shape[0], ids.shape[1]
    i = steps
    with r.profiled():
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while True:
                with jax.profiler.TraceAnnotation("bench.train_step"):
                    m = prog(ids[i % n_pool], labels[i % n_pool])
                i += 1
                if time.perf_counter() - t0 >= r.seconds:
                    break
            jax.block_until_ready(prog.state)
            window = time.perf_counter() - t0
    r.window_closed()
    done = i - steps
    r.e2e["train_samples_per_s"] = done * batch / window
    r.counts.update(steps=done, samples=done * batch, window_s=window,
                    lookups_per_step=int(ids[0].size),
                    unique_ids=int(sum(uniq[j % n_pool] for j in range(steps, i))),
                    chips=chips, fallbacks=program.fallback_total())
    r.attempted, r.failed = done, int(not np.isfinite(float(m["loss"])))
    r.read_memory_peak()
    del prog, m

    ref = reference_readings(cfg, r.seed, ids, labels, steps, shards=chips,
                             sync_bits=traffic.get("sync_bits"))
    numbers = compare.train_numbers(readings, ref)
    r.numbers = numbers
    checks = compare.held(numbers, r.cell.limits)
    return checks, r.failed == 0 and compare.all_within(checks)
