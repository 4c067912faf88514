"""The benchmark's only window onto the program under test.

It builds the program's objects from a configuration file through the
program's public entry points (``repro.configs.dcn_ctr``, ``CTRTrainer``,
``make_ctr_dp_step``), checks that what the program built is
what the file states, and reads the program's state back for the comparison.
"""
from __future__ import annotations

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _import_program():
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def trainer_config(cfg: dict):
    """The program's ``TrainerConfig`` for a configuration file, checked
    against every size the file states."""
    _import_program()
    from repro.configs import dcn_ctr
    from repro.training.ctr_trainer import TrainerConfig

    p, emb, model, data = cfg["program"], cfg["embedding"], cfg["model"], cfg["data"]
    _, spec, dcn = getattr(dcn_ctr, p["setup"])(method=p["method"], bits=emb["bits"],
                                                scale=p["scale"])
    spec = dataclasses.replace(spec, use_kernels=p["use_kernels"],
                               pad_to_tiles=p["pad_to_tiles"])
    tcfg = TrainerConfig(spec=spec, model=model["kind"], dcn=dcn,
                         lr=cfg["optimizer"]["lr"],
                         emb_weight_decay=emb["emb_weight_decay"])
    stated = {
        "n_ids": (spec.n, data["n_ids"]), "d": (spec.d, emb["d"]),
        "bits": (spec.bits, emb["bits"]), "init_scale": (spec.init_scale, emb["init_scale"]),
        "row_optimizer": (spec.row_optimizer, emb["row_optimizer"]),
        "step_lr": (spec.alpt.step_lr, emb["step_lr"]),
        "step_weight_decay": (spec.alpt.step_weight_decay, emb["step_weight_decay"]),
        "grad_scale": (spec.alpt.grad_scale, emb["grad_scale"]),
        "rounding": (spec.alpt.rounding, emb["rounding"]),
        "fields": (dcn.n_fields, data["fields"]),
        "cross_depth": (dcn.cross_depth, model["cross_depth"]),
        "mlp_widths": (list(dcn.mlp_widths), model["mlp_widths"]),
        "dropout": (dcn.dropout, model["dropout"]),
    }
    wrong = {k: v for k, v in stated.items() if v[0] != v[1]}
    if wrong:
        raise SystemExit(f"program differs from {cfg['name']}: {wrong} (program, file)")
    return tcfg


def trainer(cfg: dict):
    _import_program()
    from repro.training.ctr_trainer import CTRTrainer

    return CTRTrainer(trainer_config(cfg))


def init_state(tr, key):
    """The trainer's initial state, made on the device in one jitted call."""
    return jax.jit(tr.init_state)(key)


def dp_step(tr, mesh, sync_bits: int):
    _import_program()
    from repro.training import data_parallel as dpm

    return dpm.make_ctr_dp_step(tr, mesh, dpm.DPConfig(sync_bits=sync_bits))


def fallback_total() -> int:
    _import_program()
    from repro.kernels import ops

    return int(ops.fallback_stats()["total_fallbacks"])


# ---------------------------------------------------------------- reading back

def named_leaves(tree) -> list:
    """[(path, leaf)] of a pytree, the path as ``jax.tree_util.keystr`` gives it."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(jax.tree_util.keystr(p), leaf) for p, leaf in leaves]


def table_values(state, n: int, d: int):
    """f32 [n, d] live table values: codes times the per-row step."""
    _import_program()
    from repro.storage import base as rowstore

    tab = state.emb_state
    codes = rowstore.logical_codes(tab.codes)[:n, :d].astype(jnp.float32)
    return codes * tab.step[:n, None]


def params(state, n: int, d: int) -> dict:
    """Every persistent parameter leaf by name: the dense tree, the table's
    values and its step sizes."""
    out = dict(named_leaves(state.dense_params))
    out["table"] = table_values(state, n, d)
    out["step"] = state.emb_state.step[:n]
    return out


def first_grads(state, n: int, d: int, b1: float) -> dict:
    """Gradients of the first step as the optimizers received them, worked
    out from Adam's first moment after one step (m = (1 - b1) g)."""
    out = {k: m / (1.0 - b1) for k, m in named_leaves(state.dense_opt.mu)}
    out["table"] = state.emb_state.mu[:n, :d] / (1.0 - b1)
    return out
