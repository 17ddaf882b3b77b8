"""Chunked selective-scan (Mamba S6) Pallas kernel.

TPU adaptation of the CUDA selective-scan: the sequence is chunked so each
chunk's x/dt/B/C tiles are DMA'd to VMEM once (grid walks chunks in the
sequential minor dimension), while the [Dm, N] state persists in f32 VMEM
scratch across chunks. Inside a chunk the recurrence runs as a fori_loop over
time steps on fully vectorized [Dm, N] state — VPU-friendly, no gather/scatter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, pick_block, use_interpret


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, *refs,
                 chunk: int):
    # refs: (h_ref,) or, returning the final state, (h_out_ref, h_ref)
    h_ref = refs[-1]
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...].astype(jnp.float32)                      # [Dm, N]

    def body(t, h):
        xt = x_ref[0, t].astype(jnp.float32)                # [Dm]
        dt = jax.nn.softplus(dt_ref[0, t].astype(jnp.float32))  # [Dm]
        bt = b_ref[0, t].astype(jnp.float32)                # [N]
        ct = c_ref[0, t].astype(jnp.float32)                # [N]
        da = jnp.exp(dt[:, None] * a)                       # [Dm, N]
        h = da * h + (dt * xt)[:, None] * bt[None, :]
        y_ref[0, t] = (h @ ct).astype(y_ref.dtype)          # [Dm]
        return h

    h_ref[...] = lax.fori_loop(0, chunk, body, h_ref[...])
    if len(refs) == 2:
        @pl.when(ci == pl.num_programs(1) - 1)
        def _state():
            refs[0][0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret",
                                             "return_state"))
def mamba_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
               C: jax.Array, D: jax.Array, *, chunk: int = 128,
               interpret: bool | None = None, return_state: bool = False):
    """x, dt: [Bz,S,Dm]; A: [Dm,N]; B,C: [Bz,S,N]; D: [Dm] -> y: [Bz,S,Dm],
    and with ``return_state`` the state after the last step, [Bz,Dm,N]
    float32 (what a decode step continues from)."""
    interpret = use_interpret() if interpret is None else interpret
    bsz, s, dm = x.shape
    n = A.shape[1]
    ch = pick_block(s, chunk)
    num_c = cdiv(s, ch)

    y_spec = pl.BlockSpec((1, ch, dm), lambda bi, ci: (bi, ci, 0))
    y_shape = jax.ShapeDtypeStruct((bsz, s, dm), x.dtype)
    if return_state:
        out_specs = [y_spec, pl.BlockSpec((1, dm, n), lambda bi, ci: (bi, 0, 0))]
        out_shape = [y_shape, jax.ShapeDtypeStruct((bsz, dm, n), jnp.float32)]
    else:
        out_specs, out_shape = y_spec, y_shape
    out = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=ch),
        grid=(bsz, num_c),
        in_specs=[
            pl.BlockSpec((1, ch, dm), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, ch, dm), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((dm, n), lambda bi, ci: (0, 0)),
            pl.BlockSpec((1, ch, n), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, ch, n), lambda bi, ci: (bi, ci, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dm, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, B, C)
    y, h = out if return_state else (out, None)
    y = y + x * D[None, None].astype(x.dtype)
    return (y, h) if return_state else y
