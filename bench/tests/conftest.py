"""Fixtures of the benchmark's own tests (``python -m pytest bench/tests``).

They run on the CPU at tiny sizes: a copy of the benchmark's files in a
temporary checkout, with a tiny dense-decoder configuration, traffic mix and
cell added as files, and the harness's look for a chip skipped.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_CONFIG = {
    "name": "tiny-decoder", "source": "tests", "family": "dense_decoder",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-06, "rope_theta": 10000, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16"}
TINY_MIX = {
    "name": "tiny", "process": "poisson", "block": 8, "order_seed": 1,
    "prompt_len": {"values": [16, 32, 64],
                   "lognormal": {"median": 32, "sigma": 0.6}},
    "max_new": {"lognormal": {"median": 8, "sigma": 0.5}, "lo": 4, "hi": 16}}
TINY_CELL = {
    "name": "tiny.chat", "config": "tiny-decoder", "traffic": "tiny",
    "driver": "serve", "chips": 1, "slots": 4, "max_len": 512,
    "rate_rps": 20.0, "ramp_s": 0.5,
    "check": {"requests": 3, "tokens": 24},
    "limits": {"worst_gap_std": 0.05}}


def write_json(path: str, data) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(data, f)


@pytest.fixture()
def checkout(tmp_path):
    """A temporary checkout: BENCHMARK.json and bench/ copied, the tiny
    cell's files added, the tiny cell listed for the serving metrics."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "yi-9b.rag" in m.get("workloads", []):
            m["workloads"].append("tiny.chat")
    write_json(os.path.join(root, "BENCHMARK.json"), spec)
    write_json(os.path.join(root, "bench", "configs", "tiny-decoder.json"),
               TINY_CONFIG)
    write_json(os.path.join(root, "bench", "traffic", "tiny.json"), TINY_MIX)
    write_json(os.path.join(root, "bench", "cells", "tiny.chat.json"),
               TINY_CELL)
    return root


@pytest.fixture()
def cpu_run(monkeypatch):
    """Runs ``bench/run.py``'s main in a checkout with the look for a chip
    skipped; returns the result line as a dict."""
    import jax

    from bench import harness
    import bench.run as bench_run

    def run(root, argv, capsys):
        monkeypatch.setattr(harness, "require_chips",
                            lambda n: jax.devices()[:n])
        monkeypatch.setattr(harness, "use_compile_cache", lambda path: None)
        monkeypatch.setattr(harness, "peaks", lambda root, kind: {
            "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
        rc = bench_run.main(argv, root=root, t_start=harness.now())
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0
        return json.loads(out[-1])
    return run
