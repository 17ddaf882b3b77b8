"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

Each ``ref_*`` function is the mathematical definition, written with plain
jnp ops at f32 precision, with no tiling/blocking — tests sweep shapes and
dtypes and assert the Pallas kernels (interpret=True on CPU) match these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ----------------------------------------------------------------- attention
def ref_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
                  scale: float | None = None, logit_soft_cap: float | None = None
                  ) -> jax.Array:
    """Dense attention. q: [B,Sq,H,D]; k,v: [B,Sk,KH,D] (GQA: H % KH == 0)."""
    b, sq, h, d = q.shape
    _, sk, kh, _ = k.shape
    assert h % kh == 0
    g = h // kh
    scale = (d ** -0.5) if scale is None else scale
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(b, sq, kh, g, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf)
    if logit_soft_cap is not None:
        logits = logit_soft_cap * jnp.tanh(logits / logit_soft_cap)
    if causal:
        qpos = jnp.arange(sq)[:, None] + (sk - sq)
        kpos = jnp.arange(sk)[None, :]
        mask = qpos >= kpos
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, vf)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def ref_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         kv_len: jax.Array | int) -> jax.Array:
    """One-token decode vs a cache. q: [B,H,D]; k,v: [B,S,KH,D]; kv_len mask."""
    b, h, d = q.shape
    _, s, kh, _ = k.shape
    g = h // kh
    qf = q.astype(jnp.float32).reshape(b, kh, g, d) * (d ** -0.5)
    logits = jnp.einsum("bkgd,bskd->bkgs", qf, k.astype(jnp.float32))
    valid = jnp.arange(s)[None, :] < jnp.asarray(kv_len).reshape(-1, 1)
    logits = jnp.where(valid[:, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


# ------------------------------------------------------------------ rmsnorm
def ref_rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * rms * w.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------- mamba scan
def ref_selective_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                       C: jax.Array, D: jax.Array, h0: jax.Array | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Selective state-space scan (Mamba S6), sequential reference.

    x, dt: [B,S,Dm]; A: [Dm,N]; B,C: [B,S,N]; D: [Dm].
    Returns (y [B,S,Dm], h_final [B,Dm,N]).
    """
    bsz, s, dm = x.shape
    n = A.shape[1]
    xf, dtf = x.astype(jnp.float32), jax.nn.softplus(dt.astype(jnp.float32))
    Af = A.astype(jnp.float32)
    Bf, Cf = B.astype(jnp.float32), C.astype(jnp.float32)
    dA = jnp.exp(dtf[..., None] * Af[None, None])            # [B,S,Dm,N]
    dBx = dtf[..., None] * Bf[:, :, None, :] * xf[..., None]  # [B,S,Dm,N]
    h = jnp.zeros((bsz, dm, n), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    ys = []
    for t in range(s):
        h = dA[:, t] * h + dBx[:, t]
        ys.append(jnp.einsum("bdn,bn->bd", h, Cf[:, t]))
    y = jnp.stack(ys, axis=1) + xf * D.astype(jnp.float32)[None, None]
    return y.astype(x.dtype), h


# ------------------------------------------------------------ moe experts
def ref_moe_experts(x: jax.Array, comb: jax.Array, wg: jax.Array,
                    wu: jax.Array, wd: jax.Array) -> jax.Array:
    """Every row through every expert, weighted: x [N,D]; comb [N,E];
    wg, wu [E,D,F]; wd [E,F,D] -> [N,D] float32."""
    xf = x.astype(jnp.float32)
    g = jnp.einsum("nd,edf->enf", xf, wg.astype(jnp.float32))
    u = jnp.einsum("nd,edf->enf", xf, wu.astype(jnp.float32))
    o = jnp.einsum("enf,efd->end", jax.nn.silu(g) * u, wd.astype(jnp.float32))
    return jnp.einsum("ne,end->nd", comb.astype(jnp.float32), o)


# -------------------------------------------------------------- alu chain
def ref_alu_chain(x: jax.Array, a: jax.Array, n: int) -> jax.Array:
    """Dependent fma chain oracle: x <- x*a + a, n times (f32 accumulate)."""
    xf, af = x.astype(jnp.float32), a.astype(jnp.float32)
    for _ in range(n):
        xf = xf * af + af
    return xf.astype(x.dtype)


# ------------------------------------------------------------------- chase
def ref_chase(ring: np.ndarray | jax.Array, start: int, steps: int) -> int:
    """Pointer-chase oracle: follow ring[p] ``steps`` times."""
    r = np.asarray(ring)
    p = int(start)
    for _ in range(steps):
        p = int(r[p])
    return p


# ------------------------------------------------------------------ matmul
def ref_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32)).astype(a.dtype)
