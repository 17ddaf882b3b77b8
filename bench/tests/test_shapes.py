"""The FLOP and byte functions against the program's own parameter count."""
import dataclasses
import json
import os

import pytest

from bench import shapes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["internlm2-20b", "yi-9b"])
def test_params_match_the_program(name):
    from bench.adapters import dense_decoder

    cfg = config(name)
    mcfg = dense_decoder.model_config(cfg)
    assert shapes.params(cfg) == mcfg.param_count()[0]
    published = dict(cfg, num_hidden_layers=cfg["reduced"][
        "num_hidden_layers"]["published"])
    full = dataclasses.replace(mcfg, n_layers=published["num_hidden_layers"])
    assert shapes.params(published) == full.param_count()[0]


def test_internlm2_and_yi_sizes_from_the_issue():
    assert shapes.params(config("internlm2-20b")) == pytest.approx(3.48e9,
                                                                   rel=0.01)
    assert shapes.params(config("yi-9b")) == pytest.approx(2.60e9, rel=0.01)
    assert shapes.kv_bytes_per_token(config("internlm2-20b")) == 24576
    assert shapes.kv_bytes_per_token(config("yi-9b")) == 24576


def test_prefill_and_decode_flops_from_shapes():
    cfg = config("yi-9b")
    per_token = 2 * (shapes.params(cfg) - 2 * 64000 * 4096 - 4096
                     - 12 * 2 * 4096)
    flops = shapes.prefill_flops(cfg, 2048)
    attn = 4 * 12 * 32 * 128 * 2048 * 2049 // 2
    assert flops == 2048 * per_token + attn + 2 * 4096 * 64000
    assert shapes.decode_flops(cfg, 1, 1) == per_token + 2 * 4096 * 64000 \
        + 4 * 12 * 32 * 128


def test_decode_bytes_count_live_keys_not_the_cache():
    cfg = config("internlm2-20b")
    base = shapes.decode_bytes(cfg, 0, 0)
    assert shapes.decode_bytes(cfg, 2, 1000) - base == \
        2 * 6144 * 2 + 1000 * 24576
    assert base == pytest.approx(2 * (shapes.params(cfg) - 92544 * 6144),
                                 rel=1e-6)
