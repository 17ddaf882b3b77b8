"""Public jit'd kernel surface.

Every kernel is exposed here with a uniform ``interpret`` policy (interpret on
CPU — this container — compiled on TPU) so models and benchmarks import from
one place. Pure-jnp oracles live in ref.py; tests sweep shapes/dtypes and
assert allclose between the two.
"""
from repro.kernels.alu_chain import alu_chain
from repro.kernels.chase import chase
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode, flash_decode_stacked
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.moe_experts import moe_experts
from repro.kernels.opchain import op_chain
from repro.kernels.rmsnorm import rmsnorm

__all__ = ["alu_chain", "chase", "flash_attention", "flash_decode",
           "flash_decode_stacked", "mamba_scan", "moe_experts", "op_chain",
           "rmsnorm"]
