"""The Jamba family on the CPU at a tiny size: a whole serving run is
correct, each planted departure from the published model turns it not
correct; the hybrid byte functions against the program's parameter count;
the two hybrid readers on synthetic steps and device operations."""
import dataclasses
import json
import os
import shutil

import pytest

from bench import harness, shapes_hybrid
from bench.tests.conftest import ROOT, TINY_MIX, write_json

SEED = 2**33 + 77
TINY_JAMBA = {
    "name": "tiny-jamba", "source": "tests", "family": "jamba",
    "attn_layer_offset": 4, "attn_layer_period": 8,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_size": 64, "intermediate_size": 96, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_state": 8, "mamba_dt_rank": 8,
    "mamba_expand": 2, "mamba_proj_bias": False, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_experts": 8, "num_experts_per_tok": 2,
    "num_hidden_layers": 8, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "vocab_size": 512,
    "torch_dtype": "bfloat16", "router_experts": 16, "experts_held": [0, 8]}
# sound bf16 runs of this cell read 0.05-0.08 on the CPU, each planted
# fault below 0.49-6.2 (seed SEED)
TINY_CELL = {
    "name": "tiny-jamba.chat", "config": "tiny-jamba", "traffic": "tiny",
    "driver": "serve", "chips": 1, "slots": 4, "max_len": 512,
    "rate_rps": 20.0, "ramp_s": 0.5,
    "check": {"requests": 3, "tokens": 24},
    "limits": {"worst_gap_std": 0.25}}
ARGS = ["--workload", "tiny-jamba.chat", "--seed", str(SEED), "--seconds",
        "2", "--trace", "0"]


@pytest.fixture()
def checkout(tmp_path):
    """A temporary checkout with the tiny Jamba cell listed wherever
    ``jamba2-mini.chat`` is."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "jamba2-mini.chat" in m.get("workloads", []):
            m["workloads"].append("tiny-jamba.chat")
    write_json(os.path.join(root, "BENCHMARK.json"), spec)
    for kind, name, data in (("configs", "tiny-jamba", TINY_JAMBA),
                             ("traffic", "tiny", TINY_MIX),
                             ("cells", "tiny-jamba.chat", TINY_CELL)):
        write_json(os.path.join(root, "bench", kind, f"{name}.json"), data)
    return root


def test_sound_run_is_correct(checkout, cpu_run, capsys):
    out = cpu_run(checkout, ARGS, capsys)
    assert out["correct"] is True
    assert out["attempted"] > 10 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}


def _attention_first(params, cfg):
    """Attention at index 0 of the period, the layers after it shifted."""
    order = [4, 0, 1, 2, 3, 5, 6, 7]
    periods = {f"l{i}": params["periods"][f"l{j}"]
               for i, j in enumerate(order)}
    return ({**params, "periods": periods},
            dataclasses.replace(cfg, period=tuple(cfg.period[j]
                                                  for j in order)))


FAULTS = {
    "renormalized_top2": lambda p, c: (
        p, dataclasses.replace(c, moe_renormalize=True)),
    "no_dbc_norms": lambda p, c: (
        p, dataclasses.replace(c, ssm_dbc_norm=False)),
    "attention_at_offset_0": _attention_first,
    "rope_applied": lambda p, c: (p, dataclasses.replace(c, pos_emb="rope")),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, checkout, cpu_run, capsys,
                                      monkeypatch):
    adapter = harness.adapter(checkout, TINY_JAMBA)
    program = adapter.program

    def planted(cfg, w):
        params, mcfg, rt = program(cfg, w)
        return (*FAULTS[fault](params, mcfg), rt)

    monkeypatch.setattr(adapter, "program", planted)
    out = cpu_run(checkout, ARGS, capsys)
    assert out["correct"] is False
    assert out["checks"]["worst_gap_std"]["value"] > 0.25


def config(name):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cfg", [config("jamba2-mini"), TINY_JAMBA],
                         ids=["jamba2-mini", "tiny"])
def test_params_match_the_program(cfg):
    from bench.adapters import jamba

    assert shapes_hybrid.params(cfg) == jamba.model_config(cfg).param_count()[0]


def test_jamba2_mini_bytes():
    cfg = config("jamba2-mini")
    assert shapes_hybrid.params(cfg) * 2 == pytest.approx(14.78e9, rel=1e-3)
    # 4 MoE layers of 8 held experts, three 4096 x 14336 bf16 matrices each
    assert 4 * shapes_hybrid.expert_bytes(cfg) == 4 * 8 * 3 * 4096 * 14336 * 2
    assert shapes_hybrid.kv_bytes_per_token(cfg) == 2 * 8 * 128 * 2
    assert shapes_hybrid.state_bytes_per_row(cfg) == \
        7 * (8192 * 16 * 4 + 3 * 8192 * 2)
    assert shapes_hybrid.expected_hit_experts(cfg, 32) == pytest.approx(
        8 * (1 - (15 / 16) ** 64))
    base = shapes_hybrid.decode_bytes(cfg, 0, 0)
    assert base == (shapes_hybrid.params(cfg) - 32768 * 4096) * 2
    assert shapes_hybrid.decode_bytes(cfg, 2, 100) - base == \
        2 * 4096 * 2 + 100 * 4096 + 2 * 2 * shapes_hybrid.state_bytes_per_row(
            cfg)


def _run(steps, device_ops=None):
    run = harness.Run(root=ROOT, cell={"name": "x"}, seed=0, seconds=10.0,
                      trace=device_ops is not None, t_start=0.0)
    run.cell["config_data"] = config("jamba2-mini")
    run.window = (100.0, 110.0)
    run.data["steps"] = steps
    if device_ops is not None:
        run.profile = {"device_ops": device_ops, "busy_s": 9.0,
                       "window_s": 10.0}
    return run


def test_decode_hbm_share_reads_the_window_steps(monkeypatch):
    monkeypatch.setattr(harness.Run, "peak", lambda self, k: 819e9)
    cfg = config("jamba2-mini")
    steps = [(99.0, 99.5, 32, 9999),             # before the window
             (100.0, 100.03, 32, 16000), (101.0, 101.03, 16, 8000)]
    need = (shapes_hybrid.decode_bytes(cfg, 32, 16000)
            + shapes_hybrid.decode_bytes(cfg, 16, 8000))
    got = harness.metric_reader(ROOT, "hybrid.decode_hbm_share").read(
        _run(steps))
    assert got == pytest.approx(100 * need / (0.06 * 819e9))
    assert harness.metric_reader(ROOT, "hybrid.decode_hbm_share").read(
        _run([(0.0, 1.0, 1, 1)])) is None


def test_expert_hbm_share_reads_the_kernel_by_name(monkeypatch):
    monkeypatch.setattr(harness.Run, "peak", lambda self, k: 819e9)
    reader = harness.metric_reader(ROOT, "moe.expert_hbm_share")
    cfg = config("jamba2-mini")
    steps = [(100.0 + 0.1 * i, 100.05 + 0.1 * i, 32, 1000) for i in range(10)]
    ops = [["%moe_experts.1 f32[32,4096]{1,0:T(8,128)}", 0.3],
           ["%moe_experts.2 f32[32,4096]{1,0:T(8,128)}", 0.2],
           ["%fusion.12", 4.0], ["%ragged-dot.3", 1.0]]
    need = 10 * 4 * shapes_hybrid.expert_bytes(cfg)
    assert reader.read(_run(steps, ops)) == pytest.approx(
        100 * need / (0.5 * 819e9))
    assert reader.read(_run(steps, ops[2:])) is None    # no such kernel
    assert reader.read(_run(steps)) is None              # untraced run
