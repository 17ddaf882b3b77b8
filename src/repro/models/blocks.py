"""Attention / MLP / MoE blocks (init + apply), logical-axis annotated."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import common
from repro.models.config import ModelConfig, Runtime
from repro.parallel.sharding import Param, annotate

Params = dict[str, Any]


# =========================================================== attention block
def init_attn(key, cfg: ModelConfig, *, cross: bool = False) -> Params:
    d, h, kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 5)
    p = {
        "norm": Param(jnp.ones((d,), cfg.pdtype), ("embed",)),
        "wq": common.dense_param(ks[0], d, h * hd, ("embed", "heads", "head_dim"),
                                 cfg.pdtype, shape=(d, h, hd)),
        "wk": common.dense_param(ks[1], d, kh * hd, ("embed", "kv_heads", "head_dim"),
                                 cfg.pdtype, shape=(d, kh, hd)),
        "wv": common.dense_param(ks[2], d, kh * hd, ("embed", "kv_heads", "head_dim"),
                                 cfg.pdtype, shape=(d, kh, hd)),
        "wo": common.dense_param(ks[3], h * hd, d, ("heads", "head_dim", "embed"),
                                 cfg.pdtype, shape=(h, hd, d)),
    }
    return p


def _w(p: Params, name: str, cd):
    return p[name].value.astype(cd)


def _project_qkv(p: Params, x, cfg: ModelConfig):
    cd = cfg.cdtype
    q = jnp.einsum("bsd,dhk->bshk", x, _w(p, "wq", cd))
    k = jnp.einsum("bsd,dhk->bshk", x, _w(p, "wk", cd))
    v = jnp.einsum("bsd,dhk->bshk", x, _w(p, "wv", cd))
    return q, k, v


def _rope(cfg: ModelConfig, q, k, positions):
    if positions is None or cfg.pos_emb == "none":
        return q, k
    if cfg.mrope_sections is not None:
        q = common.apply_mrope(q, positions, cfg.mrope_sections, cfg.rope_theta)
        k = common.apply_mrope(k, positions, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = common.apply_rope(q, positions, cfg.rope_theta)
        k = common.apply_rope(k, positions, cfg.rope_theta)
    return q, k


def _annotate_qkv(cfg: ModelConfig, q, k, v):
    if cfg.attn_parallelism == "heads":
        q = annotate(q, "batch", "seq", "act_heads", None)
        k = annotate(k, "batch", "seq", "act_heads", None)
        v = annotate(v, "batch", "seq", "act_heads", None)
    else:  # context parallel: shard q rows, replicate kv heads
        q = annotate(q, "batch", "cp_seq", None, None)
        k = annotate(k, "batch", None, None, None)
        v = annotate(v, "batch", None, None, None)
    return q, k, v


def attn_train(p: Params, x, cfg: ModelConfig, rt: Runtime, positions,
               *, causal: bool = True, kv: jax.Array | None = None,
               kv_positions=None):
    """Full-sequence attention (train / prefill). x: [B,S,D].

    ``kv``: optional encoder memory for cross-attention (bidirectional).
    """
    with jax.named_scope("attn"):
        h = (common.rmsnorm(x, p["norm"].value, cfg.norm_eps)
             if cfg.norm == "rmsnorm" else x)
        src = h if kv is None else kv
        q = jnp.einsum("bsd,dhk->bshk", h, _w(p, "wq", cfg.cdtype))
        k = jnp.einsum("bsd,dhk->bshk", src, _w(p, "wk", cfg.cdtype))
        v = jnp.einsum("bsd,dhk->bshk", src, _w(p, "wv", cfg.cdtype))
        if kv is None:
            q, k = _rope(cfg, q, k, positions)
        q, k, v = _annotate_qkv(cfg, q, k, v)
        out = common.attention(q, k, v, causal=causal and kv is None,
                               impl=rt.attn_impl, block_k=rt.block_k)
        y = jnp.einsum("bshk,hkd->bsd", out, _w(p, "wo", cfg.cdtype))
        return x + annotate(y, "batch", "seq", None), (k, v)


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Params:
    kh, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, max_len, kh, hd), dtype),
        "v": jnp.zeros((batch, max_len, kh, hd), dtype),
    }


def _annotate_cache(c, cfg: ModelConfig, rt: Runtime, lead: tuple = ()):
    """Constrain a decode K or V cache; ``lead`` names leading axes (the
    stacked cache's period axis, unsharded)."""
    if rt.cache_shard == "head_dim":
        # split-K layout: the in-place cache write stays shard-local (a
        # DUS into a seq-sharded buffer makes GSPMD all-gather the whole
        # cache every step).
        return annotate(c, *lead, "batch", None, None, "kv_hd")
    if cfg.attn_parallelism == "heads":
        return annotate(c, *lead, "batch", "kv_seq", "kv_heads", None)
    return annotate(c, *lead, "batch", "kv_seq", None, None)


def _attn_decode(p: Params, x, pos, cfg: ModelConfig, positions, write,
                 attend):
    """The one-token attention step both cache forms share: norm, q/k/v,
    RoPE, then ``write(k, v, pos_arr) -> cache`` stores the token's K/V and
    ``attend(q, cache, kv_len)`` attends over it."""
    with jax.named_scope("attn"):
        b = x.shape[0]
        h = (common.rmsnorm(x, p["norm"].value, cfg.norm_eps)
             if cfg.norm == "rmsnorm" else x)
        q, k, v = _project_qkv(p, h, cfg)
        pos_arr = jnp.asarray(pos, jnp.int32)
        if positions is None:
            positions = (jnp.full((b, 1), pos_arr, jnp.int32)
                         if pos_arr.ndim == 0 else pos_arr[:, None])
        q, k = _rope(cfg, q, k, positions)
        cache = write(k, v, pos_arr)
        out = attend(q[:, 0], cache, pos_arr + 1)
        y = jnp.einsum("bhk,hkd->bd", out,
                       p["wo"].value.astype(cfg.cdtype))[:, None]
        return x + y, cache


def attn_decode(p: Params, x, cache: Params, pos, cfg: ModelConfig, rt: Runtime,
                positions=None):
    """One-token step against one layer's cache. x: [B,1,D]; cache k/v:
    [B,Smax,KH,hd].

    ``pos`` is either a scalar (the whole batch decodes in lockstep at one
    position — the static-batch path) or a ``[B]`` int32 array of *per-row*
    positions (the continuous-batching path: every slot sits at its own
    depth, so the KV write is a per-row scatter and the attention mask a
    per-row ``kv_len``). The decoder-only scan calls
    :func:`attn_decode_stacked` instead.
    """
    def write(k, v, pos_arr):
        if pos_arr.ndim == 0:
            ck = lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), pos, axis=1)
            cv = lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), pos, axis=1)
        else:
            rows = jnp.arange(k.shape[0])
            ck = cache["k"].at[rows, pos_arr].set(
                k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[rows, pos_arr].set(
                v[:, 0].astype(cache["v"].dtype))
        return {"k": _annotate_cache(ck, cfg, rt),
                "v": _annotate_cache(cv, cfg, rt)}

    def attend(q, c, kv_len):
        return common.decode_attention(q, c["k"], c["v"], kv_len)

    return _attn_decode(p, x, pos, cfg, positions, write, attend)


def attn_decode_stacked(p: Params, x, cache: Params, period, pos,
                        cfg: ModelConfig, rt: Runtime, positions=None):
    """:func:`attn_decode` against the whole stacked cache, k/v
    [P,B,Smax,KH,hd], which rides in the layer scan's carry: the token's
    K/V is scattered in place at ``(period, row, pos)`` and attention reads
    period ``period`` of the stack itself, each row up to its ``kv_len``
    (:func:`common.decode_attention_stacked`), so nothing is written back
    and the stack is never copied.
    """
    def write(k, v, pos_arr):
        # a scalar pos is scattered to every row too: as a
        # dynamic_update_slice, XLA copies the whole stack in and out
        rows = jnp.arange(k.shape[0])
        at = (period, rows, jnp.broadcast_to(pos_arr, rows.shape))

        def put(c, new):
            c = c.at[at].set(new[:, 0].astype(c.dtype))
            return _annotate_cache(c, cfg, rt, lead=(None,))
        return {"k": put(cache["k"], k), "v": put(cache["v"], v)}

    def attend(q, c, kv_len):
        return common.decode_attention_stacked(
            q, c["k"], c["v"], period, jnp.broadcast_to(kv_len, q.shape[:1]))

    return _attn_decode(p, x, pos, cfg, positions, write, attend)


def attn_cross_decode(p: Params, x, mem_kv, cfg: ModelConfig):
    """Cross-attention decode step against precomputed encoder memory."""
    h = common.rmsnorm(x, p["norm"].value, cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"].value.astype(cfg.cdtype))
    k, v = mem_kv
    out = common.decode_attention(q[:, 0], k, v, kv_len=k.shape[1])
    y = jnp.einsum("bhk,hkd->bd", out, p["wo"].value.astype(cfg.cdtype))[:, None]
    return x + y


# ================================================================= MLP block
def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "norm": Param(jnp.ones((d,), cfg.pdtype), ("embed",)),
        "wg": common.dense_param(ks[0], d, f, ("embed", "mlp"), cfg.pdtype),
        "wu": common.dense_param(ks[1], d, f, ("embed", "mlp"), cfg.pdtype),
        "wd": common.dense_param(ks[2], f, d, ("mlp", "embed"), cfg.pdtype),
    }


def mlp_apply(p: Params, x, cfg: ModelConfig):
    with jax.named_scope("mlp"):
        h = common.rmsnorm(x, p["norm"].value, cfg.norm_eps)
        cd = cfg.cdtype
        g = jnp.einsum("bsd,df->bsf", h, _w(p, "wg", cd))
        u = jnp.einsum("bsd,df->bsf", h, _w(p, "wu", cd))
        g = annotate(jax.nn.silu(g) * u, "batch", "seq", "act_mlp")
        y = jnp.einsum("bsf,fd->bsd", g, _w(p, "wd", cd))
        return x + annotate(y, "batch", "seq", None)


# ================================================================= MoE block
def init_moe(key, cfg: ModelConfig) -> Params:
    """The router over all ``n_experts``; weights of the held ones only."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_held
    ks = jax.random.split(key, 5)
    p = {
        "norm": Param(jnp.ones((d,), cfg.pdtype), ("embed",)),
        "router": common.dense_param(ks[0], d, cfg.n_experts, ("embed", None),
                                     cfg.pdtype),
        "wg": common.dense_param(ks[1], d, f, ("experts", "embed", "expert_mlp"),
                                 cfg.pdtype, shape=(e, d, f)),
        "wu": common.dense_param(ks[2], d, f, ("experts", "embed", "expert_mlp"),
                                 cfg.pdtype, shape=(e, d, f)),
        "wd": common.dense_param(ks[3], f, d, ("experts", "expert_mlp", "embed"),
                                 cfg.pdtype, shape=(e, f, d)),
    }
    if cfg.shared_expert:
        p["shared"] = init_mlp(ks[4], cfg)
    return p


def _dispatch_indices(expert_idx: jax.Array, n_experts: int, capacity: int):
    """Sort-based dispatch within each group. expert_idx: [G, N] -> slots.

    Returns (slot [G,N] in [0, E*C] with E*C == dropped, inv_order [G,N]).
    """
    g, n = expert_idx.shape
    order = jnp.argsort(expert_idx, axis=-1, stable=True)          # [G,N]
    sorted_e = jnp.take_along_axis(expert_idx, order, axis=-1)
    gi = jnp.arange(g)[:, None]
    counts = jnp.zeros((g, n_experts), jnp.int32).at[gi, expert_idx].add(1)
    starts = jnp.cumsum(counts, axis=-1) - counts                  # exclusive
    pos_in_e = jnp.arange(n)[None, :] - jnp.take_along_axis(starts, sorted_e, axis=-1)
    keep = pos_in_e < capacity
    slot_sorted = jnp.where(keep, sorted_e * capacity + pos_in_e, n_experts * capacity)
    # unsort the slot assignment back to token order
    slot = jnp.zeros((g, n), jnp.int32).at[gi, order].set(slot_sorted)
    return slot


def _route(p: Params, h, cfg: ModelConfig):
    """The router at its published width, over all ``n_experts``: softmax,
    then the top ``top_k``, renormalized only where the configuration says.
    h: [..., D] -> (gates [..., E], weights and experts [..., k])."""
    with jax.named_scope("router"):
        logits = jnp.einsum("...d,de->...e", h.astype(jnp.float32),
                            p["router"].value.astype(jnp.float32))
        gates = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = lax.top_k(gates, cfg.top_k)
        if cfg.moe_renormalize:
            top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    return gates, top_w, top_e


def moe_apply(p: Params, x, cfg: ModelConfig, rt: Runtime):
    """Token-choice top-k MoE: dropless over the held experts
    (``cfg.moe_dropless``) or with sort-based capacity dispatch.

    For capacity dispatch, tokens are regrouped as [G, N/G] with G == data
    shards so routing stays shard-local; the dispatch scatter across the
    expert-sharded buffer is the EP boundary (GSPMD emits the
    all-to-all/all-gather there).
    """
    b, s, d = x.shape
    with jax.named_scope("moe"):
        h = common.rmsnorm(x, p["norm"].value, cfg.norm_eps)
        n_tok = b * s
        if cfg.moe_dropless:
            gates, top_w, top_e = _route(p, h.reshape(n_tok, d), cfg)
            y = _experts_dropless(p, h.reshape(n_tok, d), top_w, top_e,
                                  cfg).reshape(b, s, d)
            top_e = top_e[None]
            gates = gates[None]
        else:
            y, gates, top_e = _experts_capacity(p, h, cfg, rt)
        if "shared" in p:
            # shared expert runs densely on all tokens; reuse mlp without
            # residual
            y = y + (mlp_apply(p["shared"], x, cfg) - x)
        aux = _load_balance_loss(gates, top_e, cfg.n_experts)
        return x + annotate(y, "batch", "seq", None), aux


# A step of at most this many tokens sends every one through every held
# expert (the ``moe_experts`` kernel): below about 240 rows on a v5e (197
# TFLOP/s over 819 GB/s) that pass is bound by reading the experts' weights,
# which the grouped matmul reads as well. Decode steps fall here, prefills
# above.
DENSE_ROWS = 64


def _experts_dropless(p: Params, h, top_w, top_e, cfg: ModelConfig):
    """The held experts' part of a dropless layer, every routed token
    computed. h: [N,D]; top_w, top_e: [N,k] over all ``n_experts``.

    Each held expert's weights are read once: a few tokens go through every
    held expert, weighted by their combine weight; more go, sorted by
    expert, through a grouped matmul over the held experts."""
    n, d = h.shape
    lo, hi = cfg.held
    cd = cfg.cdtype
    wg, wu, wd = (p[w].value.astype(cd) for w in ("wg", "wu", "wd"))
    with jax.named_scope("experts"):
        if n <= DENSE_ROWS:
            from repro.kernels.moe_experts import moe_experts
            held = jnp.arange(lo, hi)
            comb = jnp.sum(jnp.where(top_e[..., None] == held, top_w[..., None],
                                     0.0), axis=1)               # [N,E_held]
            return moe_experts(h.astype(cd), comb, wg, wu, wd).astype(cd)
        k = cfg.top_k
        local = top_e.reshape(-1) - lo                           # [N*k]
        held = (local >= 0) & (local < hi - lo)
        group = jnp.where(held, local, hi - lo)    # other experts sort last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((hi - lo + 1,), jnp.int32).at[group].add(1)[:-1]
        tok = order // k
        xs = h.astype(cd)[tok]
        g = _grouped(xs, wg, sizes)
        u = _grouped(xs, wu, sizes)
        eo = _grouped(jax.nn.silu(g) * u, wd, sizes)
        # rows routed to experts held elsewhere are in no group: whatever
        # the grouped matmul left there is masked out
        w = jnp.where(held, top_w.reshape(-1), 0.0)[order]
        eo = jnp.where(held[order][:, None], eo.astype(jnp.float32), 0.0)
        y = jnp.zeros((n, d), jnp.float32).at[tok].add(eo * w[:, None])
        return y.astype(cd)


def _grouped(x, w, sizes):
    """Rows of ``x`` sorted by group times their group's matrix: x [M,K];
    w [G,K,N]; sizes [G] rows a group, in order. Megablox's grouped matmul
    (``gmm``), which on a v5e outran ``lax.ragged_dot`` at Jamba's prefill
    shapes (4.79 against 5.66 ms at 6,144 rows); rows past the groups are
    left undefined."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from repro.kernels.common import use_interpret

    m, kk = x.shape
    nn = w.shape[2]
    tm = min(512, -(-m // 128) * 128)
    xp = jnp.pad(x, ((0, -m % tm), (0, 0)))
    out = gmm(xp, w, sizes, x.dtype, (tm, min(1024, kk), min(1024, nn)),
              None, None, False, use_interpret())
    return out[:m]


def _experts_capacity(p: Params, h, cfg: ModelConfig, rt: Runtime):
    """Every expert's part under capacity dispatch: tokens past an expert's
    capacity are dropped. h: [B,S,D] -> (y, gates, top_e)."""
    b, s, d = h.shape
    e, k, cd = cfg.n_experts, cfg.top_k, cfg.cdtype
    n_tok = b * s
    g = rt.moe_groups if n_tok % max(rt.moe_groups, 1) == 0 else 1
    ng = n_tok // g
    xt = annotate(h.reshape(g, ng, d), "batch", None, None)
    gates, top_w, top_e = _route(p, xt, cfg)                       # [G,N,k]

    cap = max(int(cfg.capacity_factor * ng / e) // 8 * 8, 8)
    gi = jnp.arange(g)[:, None]
    out = jnp.zeros((g, ng, d), cd)
    for slot_k in range(k):
        slot = _dispatch_indices(top_e[..., slot_k], e, cap)       # [G,N]
        buf = jnp.zeros((g, e * cap + 1, d), cd)
        buf = buf.at[gi, slot].set(xt.astype(cd), mode="drop")
        ein = annotate(buf[:, :e * cap].reshape(g, e, cap, d),
                       "batch", "experts", None, None)
        hg = jnp.einsum("gecd,edf->gecf", ein, p["wg"].value.astype(cd))
        hu = jnp.einsum("gecd,edf->gecf", ein, p["wu"].value.astype(cd))
        hh = annotate(jax.nn.silu(hg) * hu, "batch", "experts", None, None)
        eout = jnp.einsum("gecf,efd->gecd", hh, p["wd"].value.astype(cd))
        flat = jnp.concatenate(
            [eout.reshape(g, e * cap, d), jnp.zeros((g, 1, d), cd)], axis=1)
        gathered = jnp.take_along_axis(flat, slot[..., None], axis=1)   # [G,N,D]
        out = out + gathered * top_w[..., slot_k, None].astype(cd)
    return out.reshape(b, s, d), gates, top_e


def _load_balance_loss(gates, top_e, n_experts: int) -> jax.Array:
    """Switch-style auxiliary load-balancing loss."""
    me = jnp.mean(gates, axis=(0, 1))                      # [E]
    g, n, k = top_e.shape
    gi = jnp.arange(g)[:, None, None]
    counts = jnp.zeros((g, n_experts), jnp.float32).at[
        jnp.broadcast_to(gi, top_e.shape), top_e].add(1.0)
    ce = jnp.mean(counts, axis=0) / (n * k)                # [E]
    return n_experts * jnp.sum(me * ce)
