"""Shared model components: norms, rotary embeddings, attention, losses.

Attention comes in three implementations with one math:
  * ``plain``      — einsum + mask; short sequences / smoke tests.
  * ``blockwise``  — lax.scan online-softmax over KV blocks; memory-bounded
                     (XLA-native flash equivalent), used by long prefills,
                     and serves as the reference for the Pallas kernel.
  * ``pallas``     — kernels/flash_attention, the Pallas TPU kernel.
``auto`` runs causal self-attention (one length, no offset) through the
kernel on a TPU with no sharding mesh active (:func:`flash_prefill`), and
everything else, and every call on another platform or under a mesh,
through ``blockwise`` from 4,096 keys and ``plain`` below.
GQA is native (kv heads broadcast over groups); CP (context parallelism) is
purely a matter of the logical-axis annotations callers apply.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import tracing
from repro.parallel import sharding
from repro.parallel.sharding import Param, annotate

NEG_INF = -1e30


def fit_chunk(s: int, preferred: int) -> int:
    """Largest divisor of ``s`` that is <= preferred (graceful chunking)."""
    c = max(min(preferred, s), 1)
    while s % c:
        c -= 1
    return c


# ---------------------------------------------------------------------- init
def trunc_normal(key, shape, scale: float, dtype) -> jax.Array:
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def dense_param(key, d_in: int, d_out: int, axes: tuple[str | None, ...],
                dtype, *, shape: tuple[int, ...] | None = None) -> Param:
    shape = shape or (d_in, d_out)
    return Param(trunc_normal(key, shape, (1.0 / max(d_in, 1)) ** 0.5, dtype), axes)


# ---------------------------------------------------------------------- norm
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    rms = lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms).astype(x.dtype) * w.astype(x.dtype)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return y.astype(x.dtype) * w.astype(x.dtype) + b.astype(x.dtype)


# ---------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 1e4) -> jax.Array:
    """x: [B,S,H,D]; positions: [B,S] (int). Pairwise (x0,x1) rotation."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta))                 # [D/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, sections: tuple[int, ...],
                theta: float = 1e4) -> jax.Array:
    """Qwen2-VL M-RoPE. x: [B,S,H,D]; positions: [3,B,S] (t,h,w streams).

    Each frequency band is driven by one of the three position streams,
    band widths given by ``sections`` (sum == D/2).
    """
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    freqs = jnp.asarray(rope_freqs(d, theta))                  # [D/2]
    # section id per frequency -> which position stream drives it
    sec_id = np.repeat(np.arange(len(sections)), sections)     # [D/2]
    pos = positions.astype(jnp.float32)                        # [3,B,S]
    pos_per_freq = pos[sec_id]                                 # [D/2,B,S]
    angles = jnp.moveaxis(pos_per_freq, 0, -1) * freqs         # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention
def plain_attention(q, k, v, *, causal: bool, q_offset: jax.Array | int = 0,
                    kv_len: jax.Array | None = None) -> jax.Array:
    """q: [B,Sq,H,D]; k,v: [B,Sk,KH,D]. f32 softmax.

    ``kv_len``: optional [B] valid-cache lengths (ragged batches).
    """
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    g = h // kh
    qf = q.astype(jnp.float32).reshape(b, sq, kh, g, d) * (d ** -0.5)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, k.astype(jnp.float32))
    qpos = jnp.arange(sq)[:, None] + q_offset            # [sq, sk] broadcast
    kpos = jnp.arange(sk)[None, :]
    mask = (qpos >= kpos) if causal else jnp.ones((sq, sk), bool)
    mask = jnp.broadcast_to(mask[None, None, None], logits.shape)
    if kv_len is not None:
        valid = kpos[None] < jnp.asarray(kv_len).reshape(b, 1, 1)  # [B,1,sk]
        mask &= valid[:, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(jnp.float32)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def blockwise_attention(q, k, v, *, causal: bool, block_k: int = 1024,
                        q_offset: int = 0) -> jax.Array:
    """Online-softmax over KV blocks via lax.scan: O(S·bk) live memory.

    This is what makes long prefills fit: scores are never materialized
    beyond [*, Sq, block_k].
    """
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    g = h // kh
    block_k = fit_chunk(sk, block_k)
    nk = sk // block_k
    qf = q.astype(jnp.float32).reshape(b, sq, kh, g, d) * (d ** -0.5)
    kb = k.astype(jnp.float32).reshape(b, nk, block_k, kh, d)
    vb = v.astype(jnp.float32).reshape(b, nk, block_k, kh, d)
    qpos = jnp.arange(sq)[:, None] + q_offset

    def step(carry, inputs):
        m, l, acc = carry
        kblk, vblk, ki = inputs
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, kblk)
        if causal:
            kpos = ki * block_k + jnp.arange(block_k)[None, :]
            logits = jnp.where((qpos >= kpos)[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha[..., 0, None] + jnp.einsum("bkgqs,bskd->bkgqd", p, vblk)
        return (m_new, l, acc), None

    m0 = jnp.full((b, kh, g, sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, g, sq, 1), jnp.float32)
    acc0 = jnp.zeros((b, kh, g, sq, d), jnp.float32)
    (m, l, acc), _ = lax.scan(
        step, (m0, l0, acc0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(nk)))
    out = acc / jnp.maximum(l, 1e-30)
    out = jnp.moveaxis(out, 3, 1)          # [b, sq, kh, g, d]
    return out.reshape(b, sq, h, d).astype(q.dtype)


def decode_attention(q, k, v, kv_len) -> jax.Array:
    """One-token attention vs cache. q: [B,H,D]; k,v: [B,S,KH,D].

    Works transparently with a sequence-sharded KV cache: the softmax
    reduction over S lowers to partial-softmax + cross-shard combine under
    GSPMD (flash-decoding's LSE combine).
    """
    b, h, d = q.shape
    _, s, kh, _ = k.shape
    g = h // kh
    qf = q.astype(jnp.float32).reshape(b, kh, g, d) * (d ** -0.5)
    logits = jnp.einsum("bkgd,bskd->bkgs", qf, k.astype(jnp.float32))
    valid = jnp.arange(s)[None, :] < jnp.asarray(kv_len).reshape(-1, 1)
    logits = jnp.where(valid[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def decode_attention_stacked(q, k, v, period, kv_len) -> jax.Array:
    """:func:`decode_attention` over period ``period`` of a stacked cache.
    q: [B,H,D]; k,v: [P,B,S,KH,D]; kv_len: [B].

    Lowered for a TPU with no sharding mesh active, the
    ``flash_decode_stacked`` kernel reads the period where it lies, up to
    each row's ``kv_len``. Elsewhere XLA slices the period out: on a CPU,
    and for a sharded cache, which the kernel does not partition.
    """
    def sliced(q, k, v, period, kv_len):
        return decode_attention(q, lax.dynamic_index_in_dim(k, period, keepdims=False),
                                lax.dynamic_index_in_dim(v, period, keepdims=False),
                                kv_len)

    period = jnp.asarray(period, jnp.int32)
    if sharding.current() is not None:
        return sliced(q, k, v, period, kv_len)
    from repro.kernels.flash_decode import flash_decode_stacked
    return lax.platform_dependent(
        q, k, v, period, kv_len,
        tpu=functools.partial(flash_decode_stacked, interpret=False),
        default=sliced)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_prefill(q, k, v, block_k: int = 1024,
                  interpret: bool | None = None) -> jax.Array:
    """Causal self-attention through the Pallas flash kernel at any length.
    q: [B,S,H,D]; k,v: [B,S,KH,D].

    q, k and v are padded on S to a multiple of 128, and the padded query
    rows dropped: every padded key lies after every real query, so the
    causal mask hides it. ``pallas_call`` has no reverse rule, so the
    backward pass is :func:`blockwise_attention`'s (``block_k`` keys a
    block) on the saved q, k, v.
    """
    from repro.kernels.flash_attention import flash_attention

    s = q.shape[1]
    pad = ((0, 0), (0, -s % 128), (0, 0), (0, 0))
    out = flash_attention(*(jnp.pad(x, pad) for x in (q, k, v)), causal=True,
                          interpret=interpret)
    return out[:, :s]


def _flash_prefill_fwd(q, k, v, block_k, interpret):
    return flash_prefill(q, k, v, block_k, interpret), (q, k, v)


def _flash_prefill_bwd(block_k, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(blockwise_attention, causal=True,
                                       block_k=block_k), *res)
    return vjp(g)


flash_prefill.defvjp(_flash_prefill_fwd, _flash_prefill_bwd)


def attention(q, k, v, *, causal: bool = True, impl: str = "auto",
              q_offset: int = 0, block_k: int = 1024) -> jax.Array:
    sk = k.shape[1]
    block_k = min(block_k, sk)
    if impl == "auto":
        impl = "blockwise" if sk >= 4096 else "plain"
        if (causal and q.shape[1] == sk and q_offset == 0
                and sharding.current() is None):
            # the choice rests on causality, shapes and the mesh; the
            # platform makes it when the program is lowered
            tracing.count("attn.flash_prefill")
            return lax.platform_dependent(
                q, k, v,
                tpu=functools.partial(flash_prefill, block_k=block_k,
                                      interpret=False),
                default=functools.partial(attention, causal=True, impl=impl,
                                          block_k=block_k))
    if impl == "plain":
        return plain_attention(q, k, v, causal=causal, q_offset=q_offset)
    if impl == "blockwise":
        return blockwise_attention(q, k, v, causal=causal, q_offset=q_offset,
                                   block_k=block_k)
    if impl == "pallas":
        from repro.kernels.ops import flash_attention
        return flash_attention(q, k, v, causal=causal)
    raise ValueError(impl)


# -------------------------------------------------------------------- losses
def chunked_softmax_xent(h: jax.Array, emb: jax.Array, labels: jax.Array,
                         *, chunk: int = 512, logit_dtype=jnp.float32
                         ) -> jax.Array:
    """Cross-entropy without materializing [tokens, vocab] logits.

    h: [B,S,D] final hidden; emb: [V,D] output embedding; labels: [B,S].
    Sequence is scanned in chunks; per-chunk logits live only transiently
    (and vocab stays sharded over the model axis under GSPMD).
    """
    b, s, d = h.shape
    v = emb.shape[0]
    chunk = fit_chunk(s, chunk)
    n = s // chunk
    hc = h.reshape(b, n, chunk, d)
    lc = labels.reshape(b, n, chunk)

    def step(tot, inputs):
        hx, lx = inputs
        logits = jnp.einsum("bcd,vd->bcv", hx.astype(logit_dtype),
                            emb.astype(logit_dtype))
        logits = annotate(logits, "batch", None, "act_vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lx[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    total, _ = lax.scan(step, jnp.zeros((), logit_dtype),
                        (jnp.moveaxis(hc, 1, 0), jnp.moveaxis(lc, 1, 0)))
    return total / (b * s)


def top1_logits(h_last: jax.Array, emb: jax.Array) -> jax.Array:
    """Decode-step logits: h_last [B,D] x emb [V,D] -> [B,V]."""
    logits = jnp.einsum("bd,vd->bv", h_last.astype(jnp.float32),
                        emb.astype(jnp.float32))
    return annotate(logits, "batch", "act_vocab")
