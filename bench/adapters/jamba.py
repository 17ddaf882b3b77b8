"""The system under test's view of a Jamba configuration.

Maps a configuration file (published ``config.json`` keys of the ``jamba``
model type) onto ``repro.models.ModelConfig`` and the benchmark's weights
(``bench/models/jamba.py``, stacked over periods as ``l<j>.<name>``) onto
the program's parameter tree: one period of ``attn_layer_period`` layers,
stacked over periods, each leaf boxed with its logical axes. The arrays are the
reference's own, not copies.
"""
from __future__ import annotations

from repro.models.config import ModelConfig, Runtime
from repro.parallel.sharding import Param

_ATTN = {"attn_norm": ("norm", ("embed",)),
         "wq": ("wq", ("embed", "heads", "head_dim")),
         "wk": ("wk", ("embed", "kv_heads", "head_dim")),
         "wv": ("wv", ("embed", "kv_heads", "head_dim")),
         "wo": ("wo", ("heads", "head_dim", "embed"))}
_MAMBA = {"mamba_norm": ("norm", ("embed",)),
          "in_proj": ("in_proj", ("embed", "ssm_inner")),
          "conv_w": ("conv_w", ("ssm_inner", "conv")),
          "conv_b": ("conv_b", ("ssm_inner",)),
          "x_proj": ("x_proj", ("ssm_inner", None)),
          "dt_norm": ("dt_norm", (None,)), "b_norm": ("b_norm", (None,)),
          "c_norm": ("c_norm", (None,)),
          "dt_w": ("dt_w", (None, "ssm_inner")),
          "dt_b": ("dt_b", ("ssm_inner",)),
          "a_log": ("a_log", ("ssm_inner", "ssm_state")),
          "d_skip": ("d_skip", ("ssm_inner",)),
          "out_proj": ("out_proj", ("ssm_inner", "embed"))}
_MLP = {"ffn_norm": ("norm", ("embed",)), "wg": ("wg", ("embed", "mlp")),
        "wu": ("wu", ("embed", "mlp")), "wd": ("wd", ("mlp", "embed"))}
_MOE = {"ffn_norm": ("norm", ("embed",)), "router": ("router", ("embed", None)),
        "wg": ("wg", ("experts", "embed", "expert_mlp")),
        "wu": ("wu", ("experts", "embed", "expert_mlp")),
        "wd": ("wd", ("experts", "expert_mlp", "embed"))}


def model_config(cfg: dict) -> ModelConfig:
    from bench.models.jamba import layout

    if not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"]:
        raise ValueError("the program's Mamba mixer has a convolution bias "
                         "and no projection bias")
    return ModelConfig(
        name=cfg["name"], family="hybrid",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        period=tuple(layout(cfg)), n_experts=cfg["router_experts"],
        top_k=cfg["num_experts_per_tok"], moe_renormalize=False,
        moe_dropless=True, experts_held=tuple(cfg["experts_held"]),
        ssm_state=cfg["mamba_d_state"], ssm_conv=cfg["mamba_d_conv"],
        ssm_expand=cfg["mamba_expand"], dt_rank=cfg["mamba_dt_rank"],
        ssm_dbc_norm=True, pos_emb="none",
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"])


def program(cfg: dict, w: dict):
    """``(params, ModelConfig, Runtime)`` for ``repro.serving.Engine``.

    Prefill attention takes the blockwise (online-softmax) path: the plain
    one's ``[heads, S, S]`` float32 scores (1.2 GB at 3,072 tokens) do not
    fit beside the weights. The periods are unrolled (``scan_layers``
    off): a scan would slice each period's experts out of their stack, and
    the expert kernels would read a copy (896 MB a leaf)."""
    from bench.models.jamba import layout

    def block(j, names):
        return {ours: Param(w[f"l{j}.{theirs}"], ("layers",) + axes)
                for theirs, (ours, axes) in names.items()}

    periods = {}
    for j, (mixer, ffn) in enumerate(layout(cfg)):
        periods[f"l{j}"] = {
            "mixer": block(j, _ATTN if mixer == "attn" else _MAMBA),
            "ffn": block(j, _MOE if ffn == "moe" else _MLP)}
    params = {"embed": Param(w["embed"], ("vocab", "embed")),
              "lm_head": Param(w["lm_head"], ("vocab", "embed")),
              "final_norm": Param(w["final_norm"], ("embed",)),
              "periods": periods}
    return params, model_config(cfg), Runtime(
        remat=False, attn_impl="blockwise", block_k=512, scan_layers=False)
