"""A new configuration, traffic mix, driver and per-layer metric are new
files only: the harness finds them by the names BENCHMARK.json gives."""
import json
import os

from conftest import write_json

from bench import harness

DRIVER = '''
from bench import harness


def run(run):
    run.window = (harness.now(), harness.now() + run.seconds)
    run.setup_s = 0.5
    run.data["dummy"] = run.mix["value"] * run.cfg["scale"]
    run.attempted = 3
    run.check("dummy_error", 0.0, 0.0)
'''
READER = '''
def read(run):
    return run.data.get("dummy")
'''


def test_a_dummy_cell_added_as_files_is_found(checkout, cpu_run, capsys):
    bench = os.path.join(checkout, "bench")
    write_json(os.path.join(bench, "configs", "dummy-config.json"),
               {"name": "dummy-config", "family": "none", "scale": 3.0})
    write_json(os.path.join(bench, "traffic", "dummy-mix.json"),
               {"name": "dummy-mix", "value": 7.0})
    write_json(os.path.join(bench, "cells", "dummy.cell.json"),
               {"name": "dummy.cell", "config": "dummy-config",
                "traffic": "dummy-mix", "driver": "dummy", "chips": 1})
    with open(os.path.join(bench, "drivers", "dummy.py"), "w") as f:
        f.write(DRIVER)
    with open(os.path.join(bench, "metrics", "dummy.metric.py"), "w") as f:
        f.write(READER)
    spec = json.load(open(os.path.join(checkout, "BENCHMARK.json")))
    spec["per_layer"].append({
        "name": "dummy.metric", "unit": "x", "better": "higher",
        "source": "host_clock", "layer": "dummy", "moves": "setup_s",
        "workloads": ["dummy.cell"]})
    write_json(os.path.join(checkout, "BENCHMARK.json"), spec)

    assert [m["name"] for m in harness.metrics_for(
        checkout, "dummy.cell", "per_layer")] == ["dummy.metric"]
    out = cpu_run(checkout, ["--workload", "dummy.cell", "--seed", "5",
                             "--seconds", "1", "--trace", "0"], capsys)
    assert out["correct"] is True
    assert out["metrics"] == {"setup_s": {"value": 0.5, "unit": "s"}}
    assert list(out)[-1] == "checks"


def test_compile_cache_stays_in_the_checkout(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "shared"))
    root = str(tmp_path / "checkout")
    assert harness.compile_cache_dir(root) == os.path.join(
        root, "bench", ".cache", "jax")
