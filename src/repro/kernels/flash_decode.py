"""Flash-decoding: single-token attention against a long KV cache.

The KV sequence is walked in blocks by the minor (sequential) grid dimension
with running max/sum/acc scratch — the same online softmax as prefill but with
a 1-row query. This kernel is what keeps long-context decode
latency-sane: per-step HBM traffic is exactly one pass over the KV cache, and
when the cache is sequence-sharded across chips the per-chip partials combine
with one tiny LSE all-reduce (see models/common.sharded_decode_attention).

``kv_len`` masking supports ragged caches (continuous batching).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, cdiv, pick_block, use_interpret


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                   scale: float, block_k: int, num_k: int, g: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = len_ref[0]
    k_start = ki * block_k

    @pl.when(k_start < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [g, d]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, d]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [g, bk]
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ki == num_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, kv_len: jax.Array,
                 *, block_k: int | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """q: [B,H,D]; k,v: [B,S,KH,D]; kv_len: [B] int32. Returns [B,H,D]."""
    b, h, d = q.shape
    _, s, kh, _ = k.shape
    assert h % kh == 0
    g = h // kh
    scale = float(d ** -0.5)
    interpret = use_interpret() if interpret is None else interpret
    bk = pick_block(s, block_k or 512)
    num_k = cdiv(s, bk)

    # Group queries by their kv head: [B, KH, G, D]
    qt = q.reshape(b, kh, g, d)
    kt = k.transpose(0, 2, 1, 3)   # [B, KH, S, D]
    vt = v.transpose(0, 2, 1, 3)
    kv_len = kv_len.astype(jnp.int32).reshape(b)

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=bk,
                               num_k=num_k, g=g)
    out = pl.pallas_call(
        kernel,
        grid=(b, kh, num_k),
        in_specs=[
            pl.BlockSpec((1,), lambda bi, hi, ki: (bi,)),
            pl.BlockSpec((1, 1, g, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, ki: (bi, hi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda bi, hi, ki: (bi, hi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len, qt, kt, vt)
    return out.reshape(b, h, d)


def _stacked_kernel(period_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, scale: float, block_k: int):
    del period_ref  # consumed by the index maps
    b, ki = pl.program_id(0), pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = len_ref[b]
    k_start = ki * block_k

    @pl.when(k_start < kv_len)
    def _compute():
        g = q_ref.shape[1]
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (g, block_k), 1)
        live = kpos < kv_len
        # each head sliced from the loaded block: as strided loads from the
        # refs, the step took four times as long on a v5e
        kb, vb = k_ref[...], v_ref[...]                     # [bk, KH, d]
        for h in range(q_ref.shape[0]):          # every KV head of the row
            k, v = kb[:, h, :], vb[:, h, :]                      # [bk, d]
            s = jax.lax.dot_general(
                q_ref[h].astype(k.dtype), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale      # [g, bk]
            s = jnp.where(live, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # P enters the MXU in the cache's type, as a default-precision
            # float32 dot rounds it on a TPU; the sum stays float32
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode_stacked(q: jax.Array, k: jax.Array, v: jax.Array,
                         period: jax.Array, kv_len: jax.Array, *,
                         block_k: int | None = None,
                         interpret: bool | None = None) -> jax.Array:
    """One-token GQA attention over period ``period`` of a stacked cache,
    the serving decode step's form of :func:`flash_decode`, named
    ``flash_decode_stacked`` in a trace.

    q: [B,H,D]; k,v: [P,B,S,KH,D]; period: int32 scalar; kv_len: [B]
    int32, each at least 1 -> [B,H,D]. The grid walks rows, then KV blocks
    of ``[bk,KH,D]``; a row's blocks past its last live one map to that
    block, so the pipeline fetches nothing new for them, and their compute
    is skipped. K and V are read as stored; scores, max, sum and the P.V
    accumulator are float32.
    """
    b, h, d = q.shape
    _, _, s, kh, _ = k.shape
    assert h % kh == 0
    g = h // kh
    interpret = use_interpret() if interpret is None else interpret
    # 64K elements of one head a block: K and V double-buffered take
    # 4 x KH x 128 KiB of VMEM in bfloat16
    bk = pick_block(s, block_k or (1 << 16) // d)

    def kv_block(bi, ki, per, lens):
        return per[0], bi, jnp.minimum(ki, (lens[bi] - 1) // bk), 0, 0

    def row(bi, ki, per, lens):
        return bi, 0, 0, 0

    out = pl.pallas_call(
        functools.partial(_stacked_kernel, scale=float(d ** -0.5), block_k=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, s // bk),
            in_specs=[
                pl.BlockSpec((None, kh, g, d), row),
                pl.BlockSpec((None, None, bk, kh, d), kv_block),
                pl.BlockSpec((None, None, bk, kh, d), kv_block),
            ],
            out_specs=pl.BlockSpec((None, kh, g, d), row),
            scratch_shapes=[
                pltpu.VMEM((kh, g, d), jnp.float32),
                pltpu.VMEM((kh, g, 1), jnp.float32),
                pltpu.VMEM((kh, g, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="flash_decode_stacked",
        interpret=interpret,
    )(jnp.asarray(period, jnp.int32).reshape(1),
      kv_len.astype(jnp.int32).reshape(b), q.reshape(b, kh, g, d), k, v)
    return out.reshape(b, h, d)
