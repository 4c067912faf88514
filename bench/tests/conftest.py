"""Tests of the benchmark itself, on the CPU: ``python3 -m pytest bench/tests``.

They import the benchmark's modules from ``bench/`` and the program from
``src/``; JAX runs on the CPU with Pallas kernels in interpret mode."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

#: Tiny cells for rehearsals on the CPU: the real configurations with their
#: id totals cut (and so their field cardinalities) and small batches.
#: Widths and model depths are the real ones.
TINY_SCALE = {"criteo-dcn-alpt8": 0.002, "avazu-dcn-alpt4": 0.0005}
TINY_TRAFFIC = {
    "tiny-train": {"kind": "train", "batch": 16, "pool_batches": 4, "check_steps": 3},
    "tiny-dp": {"kind": "train", "batch": 8, "pool_batches": 4, "check_steps": 3,
                "sync_bits": 8},
}
#: Limits for the tiny cells: at batch 16 the stochastic rounding of a
#: few hundred rows moves the later losses and the change far more than at
#: the real sizes.
TINY_LIMITS = {
    "train": {"loss_gap": 0.02, "grad_gap": 1e-3, "change_gap": 0.08},
    # The compressed sync rounds each replica's gradient stochastically; at
    # batch 8 a chip that noise moves the gradient norms by about 3e-3.
    "dp": {"loss_gap": 0.02, "grad_gap": 0.02, "change_gap": 0.08},
}
TINY_CELLS = [
    ("tiny-criteo-train", "criteo-dcn-alpt8", "tiny-train", 1),
    ("tiny-avazu-train", "avazu-dcn-alpt4", "tiny-train", 1),
    ("tiny-criteo-dp", "criteo-dcn-alpt8", "tiny-dp", 4),
]


def make_tiny_root(dest: pathlib.Path) -> pathlib.Path:
    """A checkout-like directory: ``bench/`` copied, tiny cells added as files."""
    import zipf

    shutil.copytree(BENCH, dest / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in list(bench["configs"]):
        cfg = json.loads((ROOT / c["file"]).read_text())
        scale = TINY_SCALE[c["name"]]
        cfg["program"]["scale"] = scale
        cfg["data"]["id_total"] = int(cfg["data"]["id_total"] * scale)
        cfg["data"]["n_ids"] = sum(zipf.field_cards(
            cfg["data"]["fields"], cfg["data"]["id_total"], cfg["data"]["card_seed"]))
        cfg["name"] = "tiny-" + c["name"]
        (dest / "bench" / "configs" / f"{cfg['name']}.json").write_text(json.dumps(cfg))
        bench["configs"].append({"name": cfg["name"], "source": c["source"],
                                 "file": f"bench/configs/{cfg['name']}.json",
                                 "reduced": ["id_total"]})
    for name, traffic in TINY_TRAFFIC.items():
        (dest / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(traffic))
    for name, config, traffic, chips in TINY_CELLS:
        bench["workloads"].append({"name": name, "config": "tiny-" + config,
                                   "traffic": traffic, "chips": chips, "why": "rehearsal"})
        kind = "dp" if chips > 1 else "train"
        (dest / "bench" / "limits" / f"{name}.json").write_text(json.dumps(TINY_LIMITS[kind]))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(name)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("checkout"))


def run_cell(root: pathlib.Path, name: str, *, seed: int = 2**31 + 11, seconds: float = 2.0,
             trace: bool = False):
    """Drive one cell in this process as ``run.py`` would, minus the look
    for a chip; returns (result line, run)."""
    import time

    import jax

    sys.path.insert(0, str(root / "bench"))
    import harness

    cell = harness.load_cell(name, root)
    run = harness.Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
                      t_start=time.monotonic(), devices=jax.devices(),
                      peaks=harness.peaks_for("TPU v5 lite"))
    checks, correct = cell.runner().run(run)
    return harness.result_line(run, checks, correct), run
