"""Decoder-only LM assembly: init / train loss / prefill / decode step.

Layers are scanned per *period* (config.period); parameters and KV caches are
stacked over periods so the HLO stays compact at 126 layers, with costs
corrected for trip counts by the static analyzer. The decode step carries the
whole stacked cache through its scan and writes each token in place at its
period's index. All functions take BOXED params (Param leaves); jit shardings
are derived from the boxes.

Named scopes (``jax.named_scope``) mark the model's parts in every op's
``op_name``, and so in a profiler trace: ``embed``, ``layers`` (the scan
over periods), ``attn`` and ``mlp`` (``models/blocks.py``) inside it, and
``head`` (final norm and logits).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import blocks, common, ssm, xlstm
from repro.models.config import Layer, ModelConfig, Runtime
from repro.parallel.sharding import Param, annotate, with_layer_axis

Params = dict[str, Any]


# ------------------------------------------------------------------- blocks
def init_block(key, layer: Layer, cfg: ModelConfig) -> Params:
    mixer, ffn = layer
    k1, k2 = jax.random.split(key)
    p: Params = {}
    if mixer == "attn":
        p["mixer"] = blocks.init_attn(k1, cfg)
    elif mixer == "mamba":
        p["mixer"] = ssm.init_mamba(k1, cfg)
    elif mixer == "mlstm":
        p["mixer"] = xlstm.init_mlstm(k1, cfg)
    elif mixer == "slstm":
        p["mixer"] = xlstm.init_slstm(k1, cfg)
    if ffn == "dense":
        p["ffn"] = blocks.init_mlp(k2, cfg)
    elif ffn == "moe":
        p["ffn"] = blocks.init_moe(k2, cfg)
    return p


def block_train(p: Params, x, layer: Layer, cfg: ModelConfig, rt: Runtime,
                positions):
    """Returns (x, aux_loss, prefill_cache)."""
    mixer, ffn = layer
    cache: Params = {}
    if mixer == "attn":
        x, (k, v) = blocks.attn_train(p["mixer"], x, cfg, rt, positions)
        cache = {"k": k.astype(cfg.cdtype), "v": v.astype(cfg.cdtype)}
    elif mixer == "mamba":
        x, cache = ssm.mamba_train(p["mixer"], x, cfg, rt)
    elif mixer == "mlstm":
        x, cache = xlstm.mlstm_train(p["mixer"], x, cfg, rt)
    elif mixer == "slstm":
        x, cache = xlstm.slstm_train(p["mixer"], x, cfg, rt)
    aux = jnp.zeros((), jnp.float32)
    if ffn == "dense":
        x = blocks.mlp_apply(p["ffn"], x, cfg)
    elif ffn == "moe":
        x, aux = blocks.moe_apply(p["ffn"], x, cfg, rt)
    return x, aux, cache


_STATE_DECODE = {"mamba": ssm.mamba_decode, "mlstm": xlstm.mlstm_decode,
                 "slstm": xlstm.slstm_decode}


def block_decode(p: Params, x, cache: Params, period, pos, layer: Layer,
                 cfg: ModelConfig, rt: Runtime, positions=None):
    """One layer's decode step against its STACKED cache (leaves [P,...]):
    returns ``x`` and the stack with row ``period`` updated in place."""
    mixer, ffn = layer
    if mixer == "attn":
        x, cache = blocks.attn_decode_stacked(p["mixer"], x, cache, period, pos,
                                              cfg, rt, positions)
    elif mixer in _STATE_DECODE:
        # recurrent states are small: read the period's, write it back whole
        x, new = _STATE_DECODE[mixer](
            p["mixer"], x,
            jax.tree_util.tree_map(
                lambda a: lax.dynamic_index_in_dim(a, period, keepdims=False),
                cache), cfg)
        cache = jax.tree_util.tree_map(
            lambda a, n: lax.dynamic_update_index_in_dim(
                a, n.astype(a.dtype), period, 0), cache, new)
    if ffn == "dense":
        x = blocks.mlp_apply(p["ffn"], x, cfg)
    elif ffn == "moe":
        x, _ = blocks.moe_apply(p["ffn"], x, cfg, rt)
    return x, cache


def init_block_cache(layer: Layer, cfg: ModelConfig, batch: int, max_len: int,
                     dtype) -> Params:
    mixer, _ = layer
    if mixer == "attn":
        return blocks.init_attn_cache(cfg, batch, max_len, dtype)
    if mixer == "mamba":
        return ssm.init_mamba_cache(cfg, batch, dtype)
    if mixer == "mlstm":
        return xlstm.init_mlstm_cache(cfg, batch)
    if mixer == "slstm":
        return xlstm.init_slstm_cache(cfg, batch)
    return {}


# --------------------------------------------------------------------- LM
def init_lm(key, cfg: ModelConfig) -> Params:
    kk = jax.random.split(key, 3 + cfg.n_periods)

    def init_period(k):
        ks = jax.random.split(k, len(cfg.period))
        return {f"l{i}": init_block(ks[i], layer, cfg)
                for i, layer in enumerate(cfg.period)}

    periods = jax.vmap(init_period)(kk[3:])
    params: Params = {
        "embed": Param(common.trunc_normal(kk[0], (cfg.vocab_size, cfg.d_model),
                                           cfg.d_model ** -0.5, cfg.pdtype),
                       ("vocab", "embed")),
        "periods": with_layer_axis(periods),
        "final_norm": Param(jnp.ones((cfg.d_model,), cfg.pdtype), ("embed",)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = Param(
            common.trunc_normal(kk[1], (cfg.vocab_size, cfg.d_model),
                                cfg.d_model ** -0.5, cfg.pdtype),
            ("vocab", "embed"))
    return params


def _embed_in(params: Params, cfg: ModelConfig, tokens=None, embeds=None):
    with jax.named_scope("embed"):
        if embeds is not None:
            x = embeds.astype(cfg.cdtype)
        else:
            x = params["embed"].value.astype(cfg.cdtype)[tokens]
        return annotate(x, "batch", "seq", None)


def _out_embed(params: Params, cfg: ModelConfig):
    return (params.get("lm_head") or params["embed"]).value


def _period_train(pp: Params, x, cfg: ModelConfig, rt: Runtime, positions,
                  want_cache: bool):
    aux_total = jnp.zeros((), jnp.float32)
    caches = {}
    for i, layer in enumerate(cfg.period):
        x, aux, cache = block_train(pp[f"l{i}"], x, layer, cfg, rt, positions)
        aux_total = aux_total + aux
        if want_cache:
            caches[f"l{i}"] = cache
    return x, aux_total, caches


def forward(params: Params, cfg: ModelConfig, rt: Runtime, *, tokens=None,
            embeds=None, positions=None, want_cache: bool = False):
    """Full-sequence forward. Returns (hidden [B,S,D], aux, stacked caches)."""
    x = _embed_in(params, cfg, tokens, embeds)
    b, s = x.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(carry, pp):
        x, aux = carry
        x, a, caches = _period_train(pp, x, cfg, rt, positions, want_cache)
        return (x, aux + a), caches

    body_fn = jax.checkpoint(body) if rt.remat else body
    with jax.named_scope("layers"):
        if rt.scan_layers:
            (x, aux), caches = lax.scan(
                body_fn, (x, jnp.zeros((), jnp.float32)), params["periods"])
        else:
            aux = jnp.zeros((), jnp.float32)
            caches_list = []
            for i in range(cfg.n_periods):
                pp = jax.tree_util.tree_map(lambda a, i=i: a[i],
                                            params["periods"])
                (x, aux), c = body_fn((x, aux), pp)
                caches_list.append(c)
            caches = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *caches_list) \
                if want_cache and caches_list else {}
    with jax.named_scope("head"):
        h = common.rmsnorm(x, params["final_norm"].value, cfg.norm_eps)
    return h, aux, caches


def train_loss(params: Params, batch: dict, cfg: ModelConfig, rt: Runtime,
               aux_weight: float = 0.01):
    h, aux, _ = forward(params, cfg, rt, tokens=batch.get("tokens"),
                        embeds=batch.get("embeds"),
                        positions=batch.get("positions"))
    xent = common.chunked_softmax_xent(h, _out_embed(params, cfg),
                                       batch["labels"], chunk=rt.xent_chunk)
    return xent + aux_weight * aux, {"xent": xent, "aux": aux}


# ------------------------------------------------------------------ serving
def pad_cache(cache: Params, cfg: ModelConfig, new_len: int) -> Params:
    """Grow attention KV caches (stacked: [P,B,S,KH,hd]) to ``new_len``."""
    def grow(path, a):
        key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if key in ("k", "v") and a.ndim == 5 and a.shape[2] < new_len:
            pad = [(0, 0)] * a.ndim
            pad[2] = (0, new_len - a.shape[2])
            return jnp.pad(a, pad)
        return a
    return jax.tree_util.tree_map_with_path(grow, cache)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Params:
    one = {f"l{i}": init_block_cache(layer, cfg, batch, max_len, dtype)
           for i, layer in enumerate(cfg.period)}
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros((cfg.n_periods,) + a.shape, a.dtype), one)


def prefill(params: Params, cfg: ModelConfig, rt: Runtime, *, tokens=None,
            embeds=None, positions=None, last_positions=None):
    """Process the prompt; returns (last-token logits [B,V], caches).

    ``last_positions`` ([B] int32) gathers each row's logits at its *own*
    final prompt token instead of the padded batch's last column — the
    right-padded ragged-prompt case: a row whose prompt is shorter than the
    batch's ``max_len`` must be sampled from its true last token, not from
    a pad position (causality makes that gather exact: position ``len-1``
    never attends to the padding that follows it).
    """
    h, _, caches = forward(params, cfg, rt, tokens=tokens, embeds=embeds,
                           positions=positions, want_cache=True)
    with jax.named_scope("head"):
        if last_positions is None:
            last = h[:, -1]
        else:
            last = jnp.take_along_axis(
                h, last_positions.astype(jnp.int32)[:, None, None],
                axis=1)[:, 0]
        logits = common.top1_logits(last, _out_embed(params, cfg))
    return logits, caches


def decode_step(params: Params, cache: Params, tokens, pos, cfg: ModelConfig,
                rt: Runtime, positions=None):
    """One token for the whole batch. tokens: [B,1]; pos: scalar int or [B]
    per-row positions.

    The stacked cache rides in the layer scan's carry, and each layer writes
    its token into it in place (``block_decode``): scanning the cache as
    ``xs``/``ys`` instead would slice every period's K/V out, write it back
    into a second stack and copy that whole stack out, every step.
    """
    x = _embed_in(params, cfg, tokens)

    def body(carry, xs):
        x, cache = carry
        pp, period = xs
        cache = dict(cache)
        for i, layer in enumerate(cfg.period):
            x, cache[f"l{i}"] = block_decode(pp[f"l{i}"], x, cache[f"l{i}"],
                                             period, pos, layer, cfg, rt,
                                             positions)
        return (x, cache), None

    with jax.named_scope("layers"):
        if rt.scan_layers:
            (x, new_cache), _ = lax.scan(
                body, (x, cache),
                (params["periods"], jnp.arange(cfg.n_periods, dtype=jnp.int32)))
        else:
            # periods unrolled, each indexed statically: a kernel's weight
            # operand is then the stacked leaf itself, not a sliced copy
            carry = (x, cache)
            for i in range(cfg.n_periods):
                pp = jax.tree_util.tree_map(lambda a, i=i: a[i],
                                            params["periods"])
                carry, _ = body(carry, (pp, i))
            x, new_cache = carry
    with jax.named_scope("head"):
        h = common.rmsnorm(x, params["final_norm"].value, cfg.norm_eps)
        logits = common.top1_logits(h[:, 0], _out_embed(params, cfg))
    return logits, new_cache
