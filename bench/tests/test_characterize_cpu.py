"""A whole characterization run on the CPU at a tiny size, the look for a
chip skipped: a sound run passes its exact checks, and each fault the cell
can have fails its number: a chase answer altered where the kernel produces
it, a row whose latency does not stand clear of its noise, a held-out
program's answer altered."""
import dataclasses
import json
import os

import pytest

from conftest import write_json

SEED = 2**33 + 99
ARGS = ["--workload", "tiny.pass", "--seed", str(SEED), "--seconds", "2",
        "--trace", "0"]


@pytest.fixture()
def tiny_pass(checkout, monkeypatch):
    from bench import heldout

    bench = os.path.join(checkout, "bench")
    write_json(os.path.join(bench, "traffic", "tinypass.json"), {
        "name": "tinypass",
        "plan": [{"plan": "clock_overhead", "args": {"opt_levels": ["O3"]}}]})
    write_json(os.path.join(bench, "cells", "tiny.pass.json"), {
        "name": "tiny.pass", "config": "inkernel-v5e", "traffic": "tinypass",
        "driver": "characterize", "chips": 1,
        "limits": {"row_noise": 1 / 3, "chase_mismatch": 0,
                   "heldout_err": 0.05}})
    spec = json.load(open(os.path.join(checkout, "BENCHMARK.json")))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "inkernel-v5e.pass" in m.get("workloads", []):
            m["workloads"].append("tiny.pass")
    write_json(os.path.join(checkout, "BENCHMARK.json"), spec)
    for k, v in dict(D=256, H=4, KH=2, HD=64, F=512, ROWS=4, CACHE=128,
                     PREFILL=128).items():
        monkeypatch.setattr(heldout, k, v)
    heldout._inputs_fn.cache_clear()
    yield checkout
    heldout._inputs_fn.cache_clear()


def test_sound_run(tiny_pass, cpu_run, capsys):
    out = cpu_run(tiny_pass, ARGS, capsys)
    assert out["checks"]["chase_mismatch"]["value"] == 0
    assert out["checks"]["heldout_err"]["value"] < 0.05
    assert out["checks"]["row_noise"]["value"] < 1 / 3
    assert out["correct"] is True
    assert set(out["metrics"]) == {"rows_per_min", "pred_accuracy", "setup_s"}
    assert out["attempted"] >= 2 and out["failed"] == 0


def test_altered_chase_answer(tiny_pass, cpu_run, capsys, monkeypatch):
    from repro.kernels import chase as chase_mod

    real = chase_mod.chase
    monkeypatch.setattr(chase_mod, "chase",
                        lambda *a, **k: real(*a, **k) + 16)
    out = cpu_run(tiny_pass, ARGS, capsys)
    assert out["checks"]["chase_mismatch"]["value"] == 2
    assert out["correct"] is False


def test_unresolved_row(tiny_pass, cpu_run, capsys, monkeypatch):
    from repro.api import probes

    real = probes.ClockOverheadProbe.run_prepared

    def noisy(self, ctx, prepared):
        rec = real(self, ctx, prepared)
        return dataclasses.replace(rec, mad_ns=rec.latency_ns)

    monkeypatch.setattr(probes.ClockOverheadProbe, "run_prepared", noisy)
    out = cpu_run(tiny_pass, ARGS, capsys)
    assert out["checks"]["row_noise"]["value"] == pytest.approx(1.0)
    assert out["correct"] is False


class _Scaled:
    """A jitted program whose compiled answers come out 10% too large."""

    def __init__(self, fn):
        self.fn = fn

    def lower(self, *args):
        lowered = self.fn.lower(*args)
        return type("Lowered", (), {"compile": lambda _: _Compiled(
            lowered.compile())})()


class _Compiled:
    def __init__(self, compiled):
        self.compiled = compiled

    def __call__(self, *args):
        return self.compiled(*args) * 1.1

    def as_text(self):
        return self.compiled.as_text()


def test_altered_heldout_answer(tiny_pass, cpu_run, capsys, monkeypatch):
    from bench import heldout

    real = heldout.program
    monkeypatch.setattr(heldout, "program",
                        lambda *a, **k: _Scaled(real(*a, **k)))
    out = cpu_run(tiny_pass, ARGS, capsys)
    assert out["checks"]["heldout_err"]["value"] > 0.05
    assert out["correct"] is False
