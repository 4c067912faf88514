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
import typing  # noqa: E402

import pytest  # noqa: E402

REHEARSAL = pathlib.Path("bench", "tests", "rehearsal")


class TinyCell(typing.NamedTuple):
    """A rehearsal of a real workload: its name, the workload's entry in
    ``BENCHMARK.json``, the chips it runs on (virtual devices on the CPU),
    what it sets in the workload's traffic, and the rehearsal file's cut."""

    name: str
    workload: dict
    chips: int
    traffic: dict
    cut: dict


def rehearsal_file(root: pathlib.Path, config: str) -> pathlib.Path:
    """Where a configuration's rehearsal cut lives: the numbers its runner's
    ``rehearsal`` takes, and optionally ``tiny_cells``, {workload: [{"name",
    "chips", "traffic"}]}, the tiny cells built from each workload (by
    default one, ``tiny-<workload>``, on the workload's chips)."""
    return root / REHEARSAL / f"{config}.json"


def tiny_cells(root: pathlib.Path = ROOT) -> list:
    """The tiny cells of every workload whose configuration has a rehearsal
    file (``make_tiny_root`` and ``test_rehearsal`` refuse one without)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    out = []
    for w in bench["workloads"]:
        path = rehearsal_file(root, w["config"])
        if not path.exists():
            continue
        cut = json.loads(path.read_text())
        for t in cut.get("tiny_cells", {}).get(w["name"], [{"name": "tiny-" + w["name"]}]):
            out.append(TinyCell(t["name"], w, t.get("chips", w["chips"]), t.get("traffic", {}),
                                cut))
    return out


def missing_rehearsals(root: pathlib.Path = ROOT) -> list:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return [str(rehearsal_file(root, c["name"])) for c in bench["configs"]
            if not rehearsal_file(root, c["name"]).exists()]


def make_tiny_root(dest: pathlib.Path, src: pathlib.Path = ROOT) -> pathlib.Path:
    """A checkout-like directory: ``src``'s ``bench/`` copied, and beside each
    workload its tiny cells as files, each cut by its runner's ``rehearsal``
    from the configuration's rehearsal file."""
    import harness

    missing = missing_rehearsals(src)
    if missing:
        raise FileNotFoundError(f"no rehearsal file for a configuration: {missing}")
    shutil.copytree(src / "bench", dest / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((src / "BENCHMARK.json").read_text())
    for tiny in tiny_cells(src):
        real = tiny.workload["name"]
        cell = harness.load_cell(real, src)
        cfg, traffic, limits = cell.runner().rehearsal(
            cell.config, dict(cell.traffic, **tiny.traffic), cell.limits, tiny.cut)
        cfg["name"] = tiny.name
        for kind, data in (("configs", cfg), ("traffic", traffic), ("limits", limits)):
            (dest / "bench" / kind / f"{tiny.name}.json").write_text(json.dumps(data))
        bench["configs"].append({"name": tiny.name, "source": f"rehearsal of {real}",
                                 "file": f"bench/configs/{tiny.name}.json", "reduced": []})
        bench["workloads"].append({"name": tiny.name, "config": tiny.name,
                                   "traffic": tiny.name, "chips": tiny.chips,
                                   "why": f"rehearsal of {real}"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if real in m.get("workloads", ()):
                m["workloads"].append(tiny.name)
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
