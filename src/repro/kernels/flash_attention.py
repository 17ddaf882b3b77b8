"""Blockwise (flash) causal attention as a Pallas TPU kernel.

TPU-native adaptation: online-softmax accumulation in f32 VMEM scratch while
the grid walks K/V blocks in the (sequential) minor grid dimension — the
standard Pallas TPU flash pattern. GQA is expressed with *index maps* (the
same K/V block is aliased for the ``g`` query heads that share it) instead of
materializing repeated K/V in HBM: on TPU that is a pure DMA aliasing win.

Block shapes are chosen so the working set (q, k, v, acc tiles) fits VMEM and
matmul dims stay multiples of 128 for the MXU (see ``default_blocks``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, cdiv, pick_block, use_interpret


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, sq: int, sk: int,
                  block_q: int, block_k: int, num_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q + (sk - sq)      # absolute pos of first q row in kv space
    k_start = ki * block_k

    def compute():
        # q and k enter the MXU as stored; scores and the softmax state are
        # float32, and the scale applies to the float32 scores
        s = jax.lax.dot_general(q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]                                   # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)                       # [bq, 1]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # P enters the MXU in V's type, as a default-precision float32 dot
        # rounds it on a TPU; the accumulator stays float32
        v = v_ref[...]                                        # [bk, d]
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # Skip fully-masked blocks (saves ~2x on causal prefill).
        pl.when(k_start <= q_start + block_q - 1)(compute)
    else:
        compute()

    @pl.when(ki == num_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def default_blocks(sq: int, sk: int, d: int) -> tuple[int, int]:
    # VMEM budget (f32): bq*d (q) + 2*bk*d (kv) + bq*d (acc) + bq*bk (p).
    # 512x512 blocks at d=128 => ~1.5 MiB << 16 MiB VMEM; matmul dims 128-aligned.
    return pick_block(sq, 512), pick_block(sk, 512)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """q: [B,Sq,H,D]; k,v: [B,Sk,KH,D] with H % KH == 0. Returns [B,Sq,H,D].

    The dots take q, k and v in their own type with float32 accumulation,
    so float32 inputs keep float32 dots. Causal blocks above the diagonal
    are skipped.
    """
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    assert h % kh == 0, (h, kh)
    g = h // kh
    scale = float(d ** -0.5) if scale is None else scale
    interpret = use_interpret() if interpret is None else interpret

    bq, bk = default_blocks(sq, sk, d)
    if block_q:
        bq = pick_block(sq, block_q)
    if block_k:
        bk = pick_block(sk, block_k)
    num_q, num_k = cdiv(sq, bq), cdiv(sk, bk)

    # [B,H,S,D] layout inside the kernel: head-major so each grid cell streams
    # contiguous [block, d] tiles. XLA writes q and k so from the fusions that
    # make them, and a [B,S,H*D] view of the heads would cost it copies.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    q_spec = pl.BlockSpec((None, None, bq, d),
                          lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec(
        (None, None, bk, d),
        lambda bi, hi, qi, ki: (bi, hi // g, ki, 0))

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, sq=sq, sk=sk,
        block_q=bq, block_k=bk, num_k=num_k)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, num_q, num_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
