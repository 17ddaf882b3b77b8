"""Mamba (S6 selective scan) block — chunked associative-scan training path,
O(1)-state decode path, Pallas kernel opt-in (kernels/mamba_scan).

The CUDA selective-scan kernel's insight (fuse the recurrence, never
materialize [B,S,D,N] in HBM) maps to TPU as: chunk the sequence, run
``lax.associative_scan`` on VMEM-sized [B,Lc,D,N] tiles inside a lax.scan
over chunks. Cost accounting of the chunk loop is handled by the HLO static
analyzer (trip-count corrected).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import common
from repro.models.config import ModelConfig, Runtime
from repro.parallel.sharding import Param, annotate

Params = dict[str, Any]


def init_mamba(key, cfg: ModelConfig) -> Params:
    d, di, n, k, dtr = cfg.d_model, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv, cfg.dt_r
    ks = jax.random.split(key, 7)
    p = {
        "norm": Param(jnp.ones((d,), cfg.pdtype), ("embed",)),
        "in_proj": common.dense_param(ks[0], d, 2 * di, ("embed", "ssm_inner"), cfg.pdtype),
        "conv_w": Param(common.trunc_normal(ks[1], (di, k), (1.0 / k) ** 0.5, cfg.pdtype),
                        ("ssm_inner", "conv")),
        "conv_b": Param(jnp.zeros((di,), cfg.pdtype), ("ssm_inner",)),
        "x_proj": common.dense_param(ks[2], di, dtr + 2 * n, ("ssm_inner", None), cfg.pdtype),
        "dt_w": common.dense_param(ks[3], dtr, di, (None, "ssm_inner"), cfg.pdtype),
        "dt_b": Param(jnp.full((di,), -4.6, cfg.pdtype), ("ssm_inner",)),  # softplus ~= 0.01
        "a_log": Param(jnp.log(jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32),
                                                (di, n))).astype(cfg.pdtype),
                       ("ssm_inner", "ssm_state")),
        "d_skip": Param(jnp.ones((di,), cfg.pdtype), ("ssm_inner",)),
        "out_proj": common.dense_param(ks[4], di, d, ("ssm_inner", "embed"), cfg.pdtype),
    }
    if cfg.ssm_dbc_norm:
        for name, width in (("dt_norm", dtr), ("b_norm", n), ("c_norm", n)):
            p[name] = Param(jnp.ones((width,), cfg.pdtype), (None,))
    return p


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv via K shifted adds. x: [B,S,Di]; w: [Di,K]."""
    k = w.shape[1]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    s = x.shape[1]
    out = jnp.zeros_like(x)
    for j in range(k):
        out = out + pad[:, j:j + s] * w[:, j]
    return out + b


def _ssm_inputs(p: Params, h, cfg: ModelConfig):
    cd = cfg.cdtype
    xz = jnp.einsum("bsd,de->bse", h, p["in_proj"].value.astype(cd))
    x1, z = jnp.split(xz, 2, axis=-1)
    x1 = annotate(x1, "batch", "seq", "act_mlp")
    return x1, z


def _ssm_params(p: Params, x1, cfg: ModelConfig):
    """Input-dependent dt/B/C from conv'd activations (f32 for the scan),
    each RMS-normed first where ``cfg.ssm_dbc_norm`` (Jamba)."""
    cd = cfg.cdtype
    n, dtr = cfg.ssm_state, cfg.dt_r
    dbc = jnp.einsum("bsi,ie->bse", x1, p["x_proj"].value.astype(cd))
    dt_r, b_in, c_in = jnp.split(dbc, [dtr, dtr + n], axis=-1)
    if cfg.ssm_dbc_norm:
        dt_r, b_in, c_in = (common.rmsnorm(t, p[name].value, cfg.norm_eps)
                            for t, name in ((dt_r, "dt_norm"), (b_in, "b_norm"),
                                            (c_in, "c_norm")))
    dt = jnp.einsum("bsr,ri->bsi", dt_r, p["dt_w"].value.astype(cd))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_b"].value.astype(jnp.float32))
    a = -jnp.exp(p["a_log"].value.astype(jnp.float32))          # [Di,N]
    return dt, a, b_in.astype(jnp.float32), c_in.astype(jnp.float32)


def _chunk_scan(dt, a, b_in, c_in, x1, chunk: int):
    """Chunked associative scan. Shapes: dt,x1 [B,S,Di]; b,c [B,S,N];
    y comes back in ``x1``'s type.

    Each chunk's ``[B,Lc,Di,N]`` decay and input tiles are built inside the
    chunk loop from its ``[B,Lc,...]`` slices, so no ``[B,S,Di,N]`` tensor
    exists (at a 3,072-token prompt and Di 8,192 one would be 1.6 GB)."""
    bsz, s, di = x1.shape
    lc = common.fit_chunk(s, chunk)
    nc = s // lc

    def chunks(t):                                   # [B,S,...] -> [nc,B,Lc,...]
        return jnp.moveaxis(t.reshape(bsz, nc, lc, *t.shape[2:]), 1, 0)

    def combine(left, right):
        a1, u1 = left
        a2, u2 = right
        return a1 * a2, a2 * u1 + u2

    def chunk_step(h, xs):
        dt_k, x_k, b_k, c_k = xs                     # [B,Lc,Di], [B,Lc,N]
        da_k = jnp.exp(dt_k[..., None] * a)          # [B,Lc,Di,N]
        u_k = (dt_k * x_k)[..., None] * b_k[:, :, None, :]
        u0 = u_k.at[:, 0].add(da_k[:, 0] * h)
        _, acc_u = lax.associative_scan(combine, (da_k, u0), axis=1)
        y_k = jnp.einsum("bldn,bln->bld", acc_u, c_k)
        return acc_u[:, -1], y_k.astype(x1.dtype)

    h0 = jnp.zeros((bsz, di, a.shape[1]), jnp.float32)
    h_final, y = lax.scan(chunk_step, h0,
                          (chunks(dt), chunks(x1.astype(jnp.float32)),
                           chunks(b_in), chunks(c_in)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, s, di)
    return y, h_final


def mamba_train(p: Params, x, cfg: ModelConfig, rt: Runtime):
    """x: [B,S,D] -> (residual output, decode cache {h, conv})."""
    with jax.named_scope("mamba"):
        h = common.rmsnorm(x, p["norm"].value, cfg.norm_eps)
        x1, z = _ssm_inputs(p, h, cfg)
        conv_tail = x1[:, -(cfg.ssm_conv - 1):]   # pre-conv inputs for decode
        x1 = jax.nn.silu(_causal_conv(
            x1, p["conv_w"].value.astype(cfg.cdtype),
            p["conv_b"].value.astype(cfg.cdtype)))
        dt, a, b_in, c_in = _ssm_params(p, x1, cfg)
        if rt.use_pallas:
            from repro.kernels.ops import mamba_scan
            # kernel consumes raw dt (applies softplus itself); pass
            # pre-softplus
            y, h_final = mamba_scan(
                x1.astype(jnp.float32),
                jnp.log(jnp.expm1(jnp.maximum(dt, 1e-6))), a, b_in, c_in,
                p["d_skip"].value.astype(jnp.float32),
                chunk=rt.mamba_chunk, return_state=True)
        else:
            y, h_final = _chunk_scan(dt, a, b_in, c_in, x1, rt.mamba_chunk)
            y = y + x1.astype(jnp.float32) * p["d_skip"].value.astype(
                jnp.float32)
        y = (y.astype(cfg.cdtype) * jax.nn.silu(z))
        out = jnp.einsum("bsi,id->bsd", y,
                         p["out_proj"].value.astype(cfg.cdtype))
        cache = {"h": h_final, "conv": conv_tail.astype(cfg.cdtype)}
        return x + annotate(out, "batch", "seq", None), cache


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype) -> Params:
    return {
        "h": jnp.zeros((batch, cfg.ssm_inner, cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, cfg.ssm_inner), dtype),
    }


def mamba_decode(p: Params, x, cache: Params, cfg: ModelConfig):
    """One-token step. x: [B,1,D]."""
    with jax.named_scope("mamba"):
        cd = cfg.cdtype
        h = common.rmsnorm(x, p["norm"].value, cfg.norm_eps)
        x1, z = _ssm_inputs(p, h, cfg)                            # [B,1,Di]
        w = p["conv_w"].value.astype(cd)                          # [Di,K]
        hist = jnp.concatenate([cache["conv"],
                                x1.astype(cache["conv"].dtype)], axis=1)
        conv = (jnp.einsum("bki,ik->bi", hist.astype(cd), w)
                + p["conv_b"].value.astype(cd))
        x1s = jax.nn.silu(conv)[:, None]                          # [B,1,Di]
        dt, a, b_in, c_in = _ssm_params(p, x1s, cfg)
        dtq = dt[:, 0]                                            # [B,Di]
        da = jnp.exp(dtq[..., None] * a[None])                    # [B,Di,N]
        hn = da * cache["h"] + (dtq * x1s[:, 0].astype(jnp.float32))[
            ..., None] * b_in[:, 0, None, :]
        y = (jnp.einsum("bdn,bn->bd", hn, c_in[:, 0])
             + x1s[:, 0].astype(jnp.float32)
             * p["d_skip"].value.astype(jnp.float32))
        y = (y.astype(cd) * jax.nn.silu(z[:, 0]))[:, None]
        out = jnp.einsum("bsi,id->bsd", y, p["out_proj"].value.astype(cd))
        return x + out, {"h": hn, "conv": hist[:, 1:]}

