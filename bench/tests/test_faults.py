"""Each fault a cell can have, planted in the program underneath a run,
turns ``correct`` false; the same run without it is correct.  The look for
a chip is skipped; the rest of the run is the harness's own."""
import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, run_cell


def _train_step_with(monkeypatch, fault):
    from repro.training.ctr_trainer import CTRTrainer

    orig = CTRTrainer.train_step

    def patched(self, state, ids, labels):
        if fault == "unchanged":
            return state, orig(self, state, ids, labels)[1]
        half = len(ids) // 2
        return orig(self, state, ids[:half], labels[:half])

    monkeypatch.setattr(CTRTrainer, "train_step", patched)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
@pytest.mark.parametrize("name", ["tiny-criteo-train", "tiny-avazu-train"])
def test_train_faults_are_caught(tiny_root, monkeypatch, name, fault):
    _train_step_with(monkeypatch, fault)
    line, run = run_cell(tiny_root, name, seconds=1.0)
    assert line["correct"] is False, (fault, line["checks"])


DP_SCRIPT = """
import json, sys, pathlib
sys.path.insert(0, {tests!r})
from conftest import run_cell
from repro.training import data_parallel as dpm
dpm._sync_tree_mesh = lambda grads, key, dp: grads
dpm._sync_delta_mesh = lambda g, key, dp: g
line, run = run_cell(pathlib.Path({root!r}), "tiny-criteo-dp", seconds=1.0)
print(json.dumps({{"correct": line["correct"], "checks": line["checks"]}}))
"""


def test_skipped_exchange_is_caught(tiny_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = DP_SCRIPT.format(tests=str(BENCH / "tests"), root=str(tiny_root))
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is False, out["checks"]
