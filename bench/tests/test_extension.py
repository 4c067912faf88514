"""A later change adds a configuration, a traffic mix and a per-layer metric
as new files, and the harness finds them without an edit to a file that is
already there."""
import json

import harness
from conftest import make_tiny_root


def test_new_files_are_found_by_name(tmp_path):
    root = make_tiny_root(tmp_path)
    bench_dir = root / "bench"
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}

    cfg = json.loads((bench_dir / "configs" / "tiny-criteo-dcn-alpt8.json").read_text())
    cfg["name"] = "tiny-criteo-lpt8"
    (bench_dir / "configs" / "tiny-criteo-lpt8.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "tiny-train-uniform.json").write_text(json.dumps(
        {"kind": "train", "batch": 8, "pool_batches": 2, "check_steps": 3}))
    (bench_dir / "limits" / "tiny-new.json").write_text(json.dumps({"loss_gap": 1.0}))
    (bench_dir / "layer_metrics" / "steps_traced.py").write_text(
        "def read(run):\n    return float(run.counts['steps'])\n")

    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-criteo-lpt8", "source": "x",
                             "file": "bench/configs/tiny-criteo-lpt8.json", "reduced": []})
    bench["workloads"].append({"name": "tiny-new", "config": "tiny-criteo-lpt8",
                               "traffic": "tiny-train-uniform", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "steps_traced", "unit": "steps", "better": "higher",
                               "source": "program_counter", "layer": "trainer step",
                               "moves": "train_samples_per_s", "workloads": ["tiny-new"]})
    bench["end_to_end"][0]["workloads"].append("tiny-new")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("tiny-new", root)
    assert cell.config["name"] == "tiny-criteo-lpt8"
    assert cell.traffic["batch"] == 8 and cell.limits == {"loss_gap": 1.0}
    assert [m["name"] for m in cell.per_layer] == ["steps_traced"]
    assert {m["name"] for m in cell.end_to_end} == {"train_samples_per_s", "setup_s"}
    assert hasattr(cell.runner(), "run")

    class _Run:
        counts = {"steps": 7}

    metric = harness.load_module(cell.bench / "layer_metrics" / "steps_traced.py")
    assert metric.read(_Run()) == 7.0
    unchanged = {p: p.read_bytes() for p in before}
    assert unchanged == before
