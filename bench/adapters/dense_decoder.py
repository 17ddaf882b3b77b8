"""The system under test's view of a dense decoder configuration.

Maps a configuration file (published ``config.json`` keys) onto
``repro.models.ModelConfig`` and the benchmark's weights onto the program's
parameter tree: one scanned period of ``("attn", "dense")`` stacked over the
layers, each leaf boxed with its logical axes.
"""
from __future__ import annotations

from repro.models.config import ModelConfig, Runtime
from repro.parallel.sharding import Param


def model_config(cfg: dict) -> ModelConfig:
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"])


def program(cfg: dict, w: dict):
    """``(params, ModelConfig, Runtime)`` for ``repro.serving.Engine``."""
    def p(name, *axes):
        return Param(w[name], axes)

    def layer(name, *axes):
        return Param(w[name], ("layers",) + axes)

    params = {
        "embed": p("embed", "vocab", "embed"),
        "lm_head": p("lm_head", "vocab", "embed"),
        "final_norm": p("final_norm", "embed"),
        "periods": {"l0": {
            "mixer": {"norm": layer("attn_norm", "embed"),
                      "wq": layer("wq", "embed", "heads", "head_dim"),
                      "wk": layer("wk", "embed", "kv_heads", "head_dim"),
                      "wv": layer("wv", "embed", "kv_heads", "head_dim"),
                      "wo": layer("wo", "heads", "head_dim", "embed")},
            "ffn": {"norm": layer("mlp_norm", "embed"),
                    "wg": layer("wg", "embed", "mlp"),
                    "wu": layer("wu", "embed", "mlp"),
                    "wd": layer("wd", "mlp", "embed")}}},
    }
    return params, model_config(cfg), Runtime(remat=False)
