"""Operations and bytes a dense decoder needs, computed from its shapes.

The benchmark's own arithmetic, from the configuration file's published
keys: what the mathematics requires, not what a program happens to do.
A matmul of ``m x k`` by ``k x n`` is ``2 m k n`` operations. Causal
attention of a query at position ``p`` reads ``p + 1`` keys and values, at
``4 * heads * head_dim`` operations a key. Prefill needs logits of its last
position only; a decode step needs them for every row it serves.
"""
from __future__ import annotations


def _dims(cfg: dict):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, f, h, kh, d // h, cfg["num_hidden_layers"], cfg["vocab_size"]


def layer_params(cfg: dict) -> int:
    """Weights of one layer: attention, MLP and the two norms."""
    d, f, h, kh, hd, _, _ = _dims(cfg)
    return d * (h + 2 * kh) * hd + h * hd * d + 3 * d * f + 2 * d


def params(cfg: dict) -> int:
    """Every weight: layers, embedding, final norm and (untied) head."""
    d, _, _, _, _, n, v = _dims(cfg)
    head = 0 if cfg["tie_word_embeddings"] else v * d
    return n * layer_params(cfg) + v * d + d + head


def _matmul_per_token(cfg: dict) -> int:
    d, f, h, kh, hd, n, _ = _dims(cfg)
    return 2 * n * (d * (h + 2 * kh) * hd + h * hd * d + 3 * d * f)


def _attn_per_key(cfg: dict) -> int:
    _, _, h, _, hd, n, _ = _dims(cfg)
    return 4 * n * h * hd


def prefill_flops(cfg: dict, length: int) -> int:
    """Operations of one prompt of ``length`` tokens, logits of the last."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    keys = length * (length + 1) // 2
    return (length * _matmul_per_token(cfg) + keys * _attn_per_key(cfg)
            + 2 * d * v)


def decode_flops(cfg: dict, rows: int, keys: int) -> int:
    """Operations of one decode step serving ``rows`` requests that read
    ``keys`` cached positions among them (their new token's included)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return rows * (_matmul_per_token(cfg) + 2 * d * v) + keys * _attn_per_key(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    _, _, _, kh, hd, n, _ = _dims(cfg)
    return 2 * n * kh * hd * _itemsize(cfg)


def decode_bytes(cfg: dict, rows: int, keys: int) -> int:
    """Bytes one decode step has to move: every layer's weights and the
    head, once, the embedding rows of its tokens, and the live keys and
    values of the requests it serves (not the cache's whole length)."""
    d, _, _, _, _, n, v = _dims(cfg)
    size = _itemsize(cfg)
    weights = (n * layer_params(cfg) + d + v * d) * size
    return weights + rows * d * size + keys * kv_bytes_per_token(cfg)


def _itemsize(cfg: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["torch_dtype"]]
