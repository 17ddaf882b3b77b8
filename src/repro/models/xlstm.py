"""xLSTM blocks: mLSTM (matrix memory, chunked-parallel train) and sLSTM
(scalar memory, sequential recurrence with exponential-gate stabilization).

mLSTM training uses the chunkwise linear-attention form: intra-chunk decayed
attention + inter-chunk [dh x dh] state carry (f32). The decode path is the
exact stabilized recurrence from the xLSTM paper (m-state tracked). The
chunked path omits the per-position m stabilizer (f32 + bounded random-init
gates keep it finite; tests compare against the recurrent reference).
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


# ------------------------------------------------------------------- mLSTM
def init_mlstm(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    di = cfg.ssm_expand * d
    h = cfg.n_heads
    ks = jax.random.split(key, 8)
    return {
        "norm": Param(jnp.ones((d,), cfg.pdtype), ("embed",)),
        "up": common.dense_param(ks[0], d, 2 * di, ("embed", "lstm_inner"), cfg.pdtype),
        "conv_w": Param(common.trunc_normal(ks[1], (di, 4), 0.5, cfg.pdtype),
                        ("lstm_inner", "conv")),
        "conv_b": Param(jnp.zeros((di,), cfg.pdtype), ("lstm_inner",)),
        "wq": common.dense_param(ks[2], di, di, ("lstm_inner", None), cfg.pdtype),
        "wk": common.dense_param(ks[3], di, di, ("lstm_inner", None), cfg.pdtype),
        "wv": common.dense_param(ks[4], di, di, ("lstm_inner", None), cfg.pdtype),
        "wi": common.dense_param(ks[5], di, h, ("lstm_inner", None), cfg.pdtype),
        "wf": common.dense_param(ks[6], di, h, ("lstm_inner", None), cfg.pdtype),
        "gn": Param(jnp.ones((di,), cfg.pdtype), ("lstm_inner",)),
        "down": common.dense_param(ks[7], di, d, ("lstm_inner", "embed"), cfg.pdtype),
    }


def _mlstm_qkvif(p: Params, x, cfg: ModelConfig):
    cd = cfg.cdtype
    h = common.rmsnorm(x, p["norm"].value, cfg.norm_eps)
    up = jnp.einsum("bsd,de->bse", h, p["up"].value.astype(cd))
    xm, z = jnp.split(up, 2, axis=-1)                       # [B,S,Di]
    xm = annotate(xm, "batch", "seq", "act_mlp")
    from repro.models.ssm import _causal_conv
    xc = jax.nn.silu(_causal_conv(xm, p["conv_w"].value.astype(cd),
                                  p["conv_b"].value.astype(cd)))
    nh = cfg.n_heads
    b, s, di = xc.shape
    dh = di // nh
    q = jnp.einsum("bsi,ij->bsj", xc, p["wq"].value.astype(cd)).reshape(b, s, nh, dh)
    k = jnp.einsum("bsi,ij->bsj", xc, p["wk"].value.astype(cd)).reshape(b, s, nh, dh)
    v = jnp.einsum("bsi,ij->bsj", xm, p["wv"].value.astype(cd)).reshape(b, s, nh, dh)
    ig = jnp.einsum("bsi,ih->bsh", xc.astype(jnp.float32),
                    p["wi"].value.astype(jnp.float32)) - 4.0   # small init inputs
    fg = jnp.einsum("bsi,ih->bsh", xc.astype(jnp.float32),
                    p["wf"].value.astype(jnp.float32)) + 4.0   # long memory init
    return q, k, v, ig, fg, z, xm


def _mlstm_chunked(q, k, v, ig, fg, chunk: int):
    """Chunkwise parallel mLSTM. q,k,v: [B,S,H,dh]; ig,fg: [B,S,H] (f32)."""
    b, s, nh, dh = q.shape
    lc = common.fit_chunk(s, chunk)
    nc = s // lc
    scale = dh ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    logf = jax.nn.log_sigmoid(fg)                              # [B,S,H]

    def reshape_c(t, feat):
        return t.reshape((b, nc, lc) + feat)

    qc, kc, vc = (reshape_c(t, (nh, dh)) for t in (qf, kf, vf))
    ic, fc = reshape_c(ig, (nh,)), reshape_c(logf, (nh,))

    def chunk_step(carry, xs):
        c_state, n_state = carry                               # [B,H,dh,dh], [B,H,dh]
        qk, kk, vk, ik, fk = xs                                # [B,Lc,...]
        fcum = jnp.cumsum(fk, axis=1)                          # [B,Lc,H]
        ftot = fcum[:, -1]                                     # [B,H]
        # intra-chunk decayed attention
        di_ = fcum[:, :, None] - fcum[:, None, :] + ik[:, None, :]   # [B,i,j,H]
        tri = jnp.tril(jnp.ones((lc, lc), bool))
        dmat = jnp.where(tri[None, :, :, None], jnp.exp(di_), 0.0)
        sc = jnp.einsum("bihd,bjhd->bijh", qk, kk) * dmat
        h_intra = jnp.einsum("bijh,bjhd->bihd", sc, vk)
        norm_intra = jnp.sum(sc, axis=2)                       # [B,i,H]
        # inter-chunk contribution
        decay_i = jnp.exp(fcum)                                # [B,Lc,H]
        h_inter = jnp.einsum("bihd,bhde->bihe", qk * decay_i[..., None], c_state)
        norm_inter = jnp.einsum("bihd,bhd->bih", qk * decay_i[..., None], n_state)
        norm = jnp.maximum(jnp.abs(norm_intra + norm_inter), 1.0)
        h_out = (h_intra + h_inter) / norm[..., None]
        # state update
        dec_j = jnp.exp(ftot[:, None] - fcum + ik)             # [B,Lc,H]
        c_new = jnp.exp(ftot)[..., None, None] * c_state + \
            jnp.einsum("bjhd,bjhe->bhde", kk * dec_j[..., None], vk)
        n_new = jnp.exp(ftot)[..., None] * n_state + \
            jnp.sum(kk * dec_j[..., None], axis=1)
        return (c_new, n_new), h_out

    c0 = jnp.zeros((b, nh, dh, dh), jnp.float32)
    n0 = jnp.zeros((b, nh, dh), jnp.float32)
    (cf, nf), hs = lax.scan(chunk_step, (c0, n0),
                            tuple(jnp.moveaxis(t, 1, 0) for t in (qc, kc, vc, ic, fc)))
    h = jnp.moveaxis(hs, 0, 1).reshape(b, s, nh * dh)
    return h, (cf, nf)


def mlstm_train(p: Params, x, cfg: ModelConfig, rt: Runtime):
    q, k, v, ig, fg, z, xm = _mlstm_qkvif(p, x, cfg)
    h, (cf, nf) = _mlstm_chunked(q, k, v, ig, fg, rt.mlstm_chunk)
    h = (common.rmsnorm(h.astype(cfg.cdtype), p["gn"].value, cfg.norm_eps)
         * jax.nn.silu(z))
    out = jnp.einsum("bsi,id->bsd", h, p["down"].value.astype(cfg.cdtype))
    cache = {"c": cf, "n": nf, "m": jnp.zeros(cf.shape[:2], jnp.float32),
             "conv": xm[:, -3:].astype(jnp.float32)}
    return x + annotate(out, "batch", "seq", None), cache


def init_mlstm_cache(cfg: ModelConfig, batch: int) -> Params:
    di = cfg.ssm_expand * cfg.d_model
    nh = cfg.n_heads
    dh = di // nh
    return {
        "c": jnp.zeros((batch, nh, dh, dh), jnp.float32),
        "n": jnp.zeros((batch, nh, dh), jnp.float32),
        "m": jnp.full((batch, nh), -1e30, jnp.float32),
        "conv": jnp.zeros((batch, 3, di), jnp.float32),
    }


def mlstm_decode(p: Params, x, cache: Params, cfg: ModelConfig):
    """Exact stabilized recurrence (one step). x: [B,1,D]."""
    cd = cfg.cdtype
    hN = common.rmsnorm(x, p["norm"].value, cfg.norm_eps)
    up = jnp.einsum("bsd,de->bse", hN, p["up"].value.astype(cd))
    xm, z = jnp.split(up, 2, axis=-1)
    hist = jnp.concatenate([cache["conv"], xm[:, 0][:, None].astype(jnp.float32)], axis=1)
    w = p["conv_w"].value.astype(jnp.float32)
    conv = jnp.einsum("bki,ik->bi", hist, w) + p["conv_b"].value.astype(jnp.float32)
    xc = jax.nn.silu(conv)                                     # [B,Di]
    nh = cfg.n_heads
    b, di = xc.shape
    dh = di // nh
    f32 = jnp.float32
    q = (xc @ p["wq"].value.astype(f32)).reshape(b, nh, dh) * dh ** -0.5
    k = (xc @ p["wk"].value.astype(f32)).reshape(b, nh, dh)
    v = (xm[:, 0].astype(f32) @ p["wv"].value.astype(f32)).reshape(b, nh, dh)
    ig = xc @ p["wi"].value.astype(f32) - 4.0                  # [B,H]
    fg = jax.nn.log_sigmoid(xc @ p["wf"].value.astype(f32) + 4.0)
    m_new = jnp.maximum(fg + cache["m"], ig)
    fs = jnp.exp(fg + cache["m"] - m_new)[..., None]
    is_ = jnp.exp(ig - m_new)[..., None]
    c_new = fs[..., None] * cache["c"] + is_[..., None] * k[..., None] * v[..., None, :]
    n_new = fs * cache["n"] + is_ * k
    num = jnp.einsum("bhd,bhde->bhe", q, c_new)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, n_new)), jnp.exp(-m_new))
    h = (num / den[..., None]).reshape(b, di)
    h = common.rmsnorm(h.astype(cd), p["gn"].value, cfg.norm_eps) * jax.nn.silu(z[:, 0])
    out = (h @ p["down"].value.astype(cd))[:, None]
    return x + out, {"c": c_new, "n": n_new, "m": m_new, "conv": hist[:, 1:]}


# ------------------------------------------------------------------- sLSTM
def init_slstm(key, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    nh = cfg.n_heads
    dh = d // nh
    ks = jax.random.split(key, 3)
    return {
        "norm": Param(jnp.ones((d,), cfg.pdtype), ("embed",)),
        "w": common.dense_param(ks[0], d, 4 * d, ("embed", "lstm_inner"), cfg.pdtype),
        "r": Param(common.trunc_normal(ks[1], (nh, dh, 4 * dh), dh ** -0.5, cfg.pdtype),
                   (None, None, None)),
        "b": Param(jnp.zeros((4 * d,), cfg.pdtype), ("lstm_inner",)),
        "gn": Param(jnp.ones((d,), cfg.pdtype), ("embed",)),
        "out": common.dense_param(ks[2], d, d, ("embed", "embed2"), cfg.pdtype),
    }


def _slstm_cell(wx_t, state, r, nh, dh):
    """wx_t: [B,4D] precomputed input path; state: (c,n,h,m) each [B,D]."""
    c, n, h, m = state
    b = wx_t.shape[0]
    hh = h.reshape(b, nh, dh)
    rec = jnp.einsum("bhd,hde->bhe", hh, r).reshape(b, 4 * nh * dh)
    gates = wx_t + rec
    zt, it, ft, ot = jnp.split(gates, 4, axis=-1)              # [B,D] each
    zt = jnp.tanh(zt)
    ot = jax.nn.sigmoid(ot)
    logf = jax.nn.log_sigmoid(ft)
    m_new = jnp.maximum(logf + m, it)
    i_ = jnp.exp(it - m_new)
    f_ = jnp.exp(logf + m - m_new)
    c_new = f_ * c + i_ * zt
    n_new = f_ * n + i_
    h_new = ot * c_new / jnp.maximum(n_new, 1.0)
    return (c_new, n_new, h_new, m_new)


def slstm_train(p: Params, x, cfg: ModelConfig, rt: Runtime):
    cd = cfg.cdtype
    b, s, d = x.shape
    nh = cfg.n_heads
    dh = d // nh
    hN = common.rmsnorm(x, p["norm"].value, cfg.norm_eps)
    wx = (jnp.einsum("bsd,de->bse", hN, p["w"].value.astype(cd))
          + p["b"].value.astype(cd)).astype(jnp.float32)
    r = p["r"].value.astype(jnp.float32)

    def step(state, wx_t):
        new = _slstm_cell(wx_t, state, r, nh, dh)
        return new, new[2]

    z = jnp.zeros((b, d), jnp.float32)
    init = (z, z, z, jnp.full((b, d), -1e30, jnp.float32))
    (c, n, hS, m), hs = lax.scan(step, init, jnp.moveaxis(wx, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).astype(cd)                      # [B,S,D]
    h = common.rmsnorm(h, p["gn"].value, cfg.norm_eps)
    out = jnp.einsum("bsd,de->bse", h, p["out"].value.astype(cd))
    cache = {"c": c, "n": n, "h": hS, "m": m}
    return x + annotate(out, "batch", "seq", None), cache


def init_slstm_cache(cfg: ModelConfig, batch: int) -> Params:
    d = cfg.d_model
    z = jnp.zeros((batch, d), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": jnp.full((batch, d), -1e30, jnp.float32)}


def slstm_decode(p: Params, x, cache: Params, cfg: ModelConfig):
    cd = cfg.cdtype
    nh = cfg.n_heads
    d = cfg.d_model
    dh = d // nh
    hN = common.rmsnorm(x, p["norm"].value, cfg.norm_eps)
    wx = (jnp.einsum("bsd,de->bse", hN, p["w"].value.astype(cd))
          + p["b"].value.astype(cd)).astype(jnp.float32)[:, 0]
    state = (cache["c"], cache["n"], cache["h"], cache["m"])
    c, n, h, m = _slstm_cell(wx, state, p["r"].value.astype(jnp.float32), nh, dh)
    hx = common.rmsnorm(h.astype(cd), p["gn"].value, cfg.norm_eps)
    out = (hx @ p["out"].value.astype(cd))[:, None]
    return x + out, {"c": c, "n": n, "h": h, "m": m}
