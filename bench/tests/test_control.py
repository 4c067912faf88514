"""The control comes out as not correct: the plain reference computed in
bfloat16, put in the program's place, fails at least one of each cell's
limits.  On the CPU, at a size a test run holds: the real widths, depths
and batch, the id space cut to a hundredth (the bfloat16 error that fails
the gradient comes from summing a batch's occurrences of the hottest rows,
so the batch stays whole)."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import compare
import harness
import zipf
from conftest import ROOT

CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _small(cfg: dict) -> dict:
    cfg = json.loads(json.dumps(cfg))
    d = cfg["data"]
    d["id_total"] = int(d["id_total"] * 0.01)
    d["n_ids"] = sum(zipf.field_cards(d["fields"], d["id_total"], d["card_seed"]))
    return cfg


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_a_limit(name):
    cell = harness.load_cell(name)
    cfg = _small(cell.config)
    drv = cell.runner()
    seed = 2**31 + 5
    chips = cell.workload["chips"]
    traffic = dict(cell.traffic, pool_batches=3)
    ids, labels, _ = drv.make_pool(cfg, traffic, seed, chips)
    kw = dict(shards=chips, sync_bits=traffic.get("sync_bits"))
    ref = drv.reference_readings(cfg, seed, ids, labels, 3, **kw)
    low = drv.reference_readings(cfg, seed, ids, labels, 3, dtype=jnp.bfloat16, **kw)
    numbers = compare.train_numbers(low, ref)
    checks = compare.held(numbers, cell.limits)
    assert not compare.all_within(checks), checks
    assert np.isfinite([c["value"] for c in checks.values()]).all()
