"""Jamba-v0.1 52B: 32L d4096 32H(kv8) ff14336 v65536, Mamba+attention 1:7
interleave, MoE 16e top-2 every other layer [arXiv:2403.19887; hf]; the
same shape as Jamba 1.5/2 Mini. In each period of 8, layer 4 is attention
(``attn_layer_offset`` 4) and the odd layers are MoE
(``expert_layer_offset`` 1, period 2). Attention takes no positional
encoding; the Mamba mixer RMS-norms dt, B and C after ``x_proj``; the
router's top-2 weights are the softmax over 16, not renormalized; MoE is
dropless."""
from repro.configs.registry import ArchSpec, register
from repro.models.config import ModelConfig

_PERIOD = (("mamba", "dense"), ("mamba", "moe"), ("mamba", "dense"),
           ("mamba", "moe"), ("attn", "dense"), ("mamba", "moe"),
           ("mamba", "dense"), ("mamba", "moe"))
_JAMBA = dict(period=_PERIOD, top_k=2, pos_emb="none", ssm_dbc_norm=True,
              moe_renormalize=False, moe_dropless=True, tie_embeddings=False)


@register("jamba-v0.1-52b")
def spec() -> ArchSpec:
    cfg = ModelConfig(
        name="jamba-v0.1-52b", family="hybrid",
        n_layers=32, d_model=4096, n_heads=32, n_kv_heads=8, d_ff=14336,
        vocab_size=65536, n_experts=16, ssm_state=16, ssm_conv=4,
        ssm_expand=2, dt_rank=256, param_dtype="bfloat16",
        attn_parallelism="heads", fsdp=True, **_JAMBA)
    smoke = ModelConfig(
        name="jamba-smoke", family="hybrid",
        n_layers=8, d_model=64, n_heads=4, n_kv_heads=2, d_ff=96,
        vocab_size=512, n_experts=4, ssm_state=8, **_JAMBA)
    return ArchSpec(cfg, smoke)
