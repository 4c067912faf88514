"""CPU rehearsal of every cell: the harness loads each cell from its files
and drives it end to end at a tiny size; the real run refuses a backend
that is not a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, TINY_CELLS, run_cell

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _run_py(cwd, *args, timeout=120):
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd, env=ENV,
                          capture_output=True, text=True, timeout=timeout)


def test_refuses_without_a_tpu():
    p = _run_py(ROOT, "--workload", "criteo-alpt8-train", "--seed", "1", "--seconds", "1",
                "--trace", "0")
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run_py(tmp_path, "--workload", "criteo-alpt8-train", "--seed", "1", "--seconds", "1",
                "--trace", "1")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_every_cell_loads_and_matches_the_program():
    import harness
    import program

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer and set(cell.limits)
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        program.trainer_config(cell.config)  # raises where the program differs
        cell.runner()


@pytest.mark.parametrize("name", [c[0] for c in TINY_CELLS if c[3] == 1])
@pytest.mark.parametrize("trace", [False, True])
def test_tiny_cell_runs_and_is_correct(tiny_root, name, trace):
    line, run = run_cell(tiny_root, name, trace=trace)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    if not trace:
        assert set(line["metrics"]) == {m["name"] for m in run.cell.end_to_end}
        assert all(m["value"] > 0 for m in line["metrics"].values())
    assert run.counts["fallbacks"] == 0


DP_SCRIPT = """
import json, sys
sys.path.insert(0, {tests!r})
from conftest import run_cell
import pathlib
line, run = run_cell(pathlib.Path({root!r}), "tiny-criteo-dp")
print(json.dumps({{"correct": line["correct"], "checks": line["checks"],
                  "samples": run.counts["samples"]}}))
"""


def test_tiny_dp4_cell_on_four_virtual_devices(tiny_root):
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = DP_SCRIPT.format(tests=str(BENCH / "tests"), root=str(tiny_root))
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True, out["checks"]
    assert out["samples"] > 0
