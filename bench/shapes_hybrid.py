"""Bytes and operations a Jamba configuration (Mamba, attention and MoE
layers in one period) needs, computed from its shapes.

The benchmark's own arithmetic, from the configuration file's published
keys, as ``bench/shapes.py`` is for the dense decoder: what the
mathematics requires, not what a program happens to do. Experts are the
held ones (``experts_held``); the router keeps ``router_experts`` outputs.
"""
from __future__ import annotations


def _dims(cfg: dict):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, f, h, kh, d // h


def _kinds(cfg: dict) -> list[tuple[str, str]]:
    """``(mixer, ffn)`` of every layer."""
    per = cfg["attn_layer_period"]
    return [("attn" if i % per == cfg["attn_layer_offset"] else "mamba",
             "moe" if i % cfg["expert_layer_period"]
             == cfg["expert_layer_offset"] else "dense")
            for i in range(cfg["num_hidden_layers"])]


def _held(cfg: dict) -> int:
    lo, hi = cfg["experts_held"]
    return hi - lo


def _mixer_params(cfg: dict, mixer: str) -> int:
    d, _, h, kh, hd = _dims(cfg)
    if mixer == "attn":
        return d + d * (h + 2 * kh) * hd + h * hd * d
    di, n = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    dtr, k = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    return (d + d * 2 * di + di * k + di + di * (dtr + 2 * n) + dtr + 2 * n
            + dtr * di + di + di * n + di + di * d)


def _ffn_params(cfg: dict, ffn: str) -> int:
    d, f, _, _, _ = _dims(cfg)
    if ffn == "dense":
        return d + 3 * d * f
    return d + d * cfg["router_experts"] + _held(cfg) * 3 * d * f


def params(cfg: dict) -> int:
    """Every weight held: layers (held experts only), embedding, final
    norm and (untied) head."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    head = 0 if cfg["tie_word_embeddings"] else v * d
    return (sum(_mixer_params(cfg, m) + _ffn_params(cfg, f)
                for m, f in _kinds(cfg)) + v * d + d + head)


def moe_layers(cfg: dict) -> int:
    return sum(f == "moe" for _, f in _kinds(cfg))


def expert_bytes(cfg: dict) -> int:
    """The held experts' gate, up and down matrices of one MoE layer: what
    a kernel that skips no expert reads a call."""
    d, f, _, _, _ = _dims(cfg)
    return _held(cfg) * 3 * d * f * _itemsize(cfg)


def expected_hit_experts(cfg: dict, rows: int) -> float:
    """Held experts that at least one of ``rows`` tokens is routed to, in
    expectation under uniform routing: what a kernel that skips idle
    experts reads."""
    e, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    return _held(cfg) * (1.0 - (1.0 - 1.0 / e) ** (k * rows))


def kv_bytes_per_token(cfg: dict) -> int:
    _, _, _, kh, hd = _dims(cfg)
    n_attn = sum(m == "attn" for m, _ in _kinds(cfg))
    return 2 * n_attn * kh * hd * _itemsize(cfg)


def state_bytes_per_row(cfg: dict) -> int:
    """One request's Mamba state over every Mamba layer: the float32 scan
    state and the convolution's last inputs."""
    d = cfg["hidden_size"]
    di, n = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    n_mamba = sum(m == "mamba" for m, _ in _kinds(cfg))
    return n_mamba * (4 * di * n + (cfg["mamba_d_conv"] - 1) * di
                      * _itemsize(cfg))


def decode_bytes(cfg: dict, rows: int, keys: int) -> int:
    """Bytes one decode step has to move: every layer's weights (the held
    experts all read, as a step of a few dozen rows hits nearly all of
    them), the final norm and the head, once; the embedding rows of its
    tokens; the live keys and values of the attention layers; and each
    served row's Mamba state, read and written."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    size = _itemsize(cfg)
    weights = (params(cfg) - v * d) * size
    return (weights + rows * d * size + keys * kv_bytes_per_token(cfg)
            + 2 * rows * state_bytes_per_row(cfg))


def _itemsize(cfg: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]
