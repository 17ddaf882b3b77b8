"""Weighted pass of a few rows through every held expert (MoE decode).

A decode step routes a few rows to each expert, so its expert layer is
bound by reading the experts' weights. The kernel streams each held
expert's gate, up and down matrices through VMEM once, in tiles of the
expert width, and sends every row through every expert, the expert's output
weighted by the row's combine weight for it (zero where the router did not
pick it). The grid walks experts, then width tiles; the ``[rows, d_model]``
float32 output stays in VMEM across the grid and accumulates every tile.
Its name in a trace is ``moe_experts``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import pick_block, use_interpret

# width tile: three [d_model, 256] bf16 tiles at d_model 4096 are 6 MiB,
# double-buffered within the v5e's default 16 MiB of scoped VMEM
WIDTH_TILE = 256


def _kernel(w_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
    a = jax.nn.silu(g) * u * w_ref[...]                  # [rows, tile]
    o_ref[...] += jnp.dot(a.astype(x.dtype), wd_ref[...],
                          preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def moe_experts(x: jax.Array, comb: jax.Array, wg: jax.Array, wu: jax.Array,
                wd: jax.Array, *, tile: int = WIDTH_TILE,
                interpret: bool | None = None) -> jax.Array:
    """x: [N,D]; comb: [N,E] combine weights; wg, wu: [E,D,F]; wd: [E,F,D]
    -> ``sum_e comb[:, e] * (silu(x wg[e]) * (x wu[e])) wd[e]``, [N,D]
    float32."""
    interpret = use_interpret() if interpret is None else interpret
    n, d = x.shape
    e, _, f = wg.shape
    tf = pick_block(f, tile)
    w = comb.astype(jnp.float32).T[:, :, None]           # [E,N,1]
    return pl.pallas_call(
        _kernel,
        grid=(e, f // tf),
        in_specs=[
            pl.BlockSpec((None, n, 1), lambda ei, j: (ei, 0, 0)),
            pl.BlockSpec((n, d), lambda ei, j: (0, 0)),
            pl.BlockSpec((None, d, tf), lambda ei, j: (ei, 0, j)),
            pl.BlockSpec((None, d, tf), lambda ei, j: (ei, 0, j)),
            pl.BlockSpec((None, tf, d), lambda ei, j: (ei, j, 0)),
        ],
        out_specs=pl.BlockSpec((n, d), lambda ei, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="moe_experts",
        interpret=interpret,
    )(w, x, wg, wu, wd)
