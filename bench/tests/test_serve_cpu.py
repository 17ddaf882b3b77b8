"""A whole serving run on the CPU at a tiny size, the look for a chip
skipped: correct where the program is sound, not correct where a served
token is altered where it is produced, and the control (the reference in
fp8) reading above the limit."""
import numpy as np

SEED = 2**33 + 77
ARGS = ["--workload", "tiny.chat", "--seed", str(SEED), "--seconds", "2",
        "--trace", "0"]


def test_sound_run_is_correct(checkout, cpu_run, capsys):
    out = cpu_run(checkout, ARGS, capsys)
    assert out["correct"] is True
    assert out["attempted"] > 10 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
                                "setup_s"}
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert out["checks"]["worst_gap_std"]["value"] <= 0.05


def test_altered_token_is_not_correct(checkout, cpu_run, capsys,
                                      monkeypatch):
    from repro.serving import engine

    step = engine.SlotPool.step

    def altered(self):
        out = step(self)
        self._tok[:, 0] = (out + 1) % self.engine.cfg.vocab_size
        return (out + 1) % self.engine.cfg.vocab_size

    monkeypatch.setattr(engine.SlotPool, "step", altered)
    out = cpu_run(checkout, ARGS, capsys)
    assert out["correct"] is False
    assert out["checks"]["worst_gap_std"]["value"] > 0.05


def test_control_is_not_correct(checkout):
    """The control: the reference computed with fp8 matmul inputs, read at
    each position by the gap of its own first choice. Over a few prompts it
    reads above the tiny cell's limit, which the program's runs stay under."""
    import json
    import os

    from bench.models import dense_decoder as ref

    cfg = json.load(open(os.path.join(checkout, "bench", "configs",
                                      "tiny-decoder.json")))
    limit = json.load(open(os.path.join(checkout, "bench", "cells",
                                        "tiny.chat.json")))["limits"]
    w = ref.init_weights(SEED, cfg)
    rng = np.random.default_rng(0)
    worst = 0.0
    for _ in range(4):
        prompt = rng.integers(1, cfg["vocab_size"], 64).tolist()
        served = rng.integers(1, cfg["vocab_size"], 16).tolist()
        _, ctl = ref.served_gaps(w, cfg, prompt, served, 512, 16,
                                 quants=("fp8",))
        worst = max(worst, float(ctl["fp8"].max()))
    assert worst > limit["worst_gap_std"]
