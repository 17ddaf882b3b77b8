"""Encoder-decoder backbone (seamless-m4t text/speech transformer).

The speech frontend is a stub: the caller feeds
precomputed frame embeddings [B, Se, D] to the encoder. The decoder is a
standard causal transformer with per-layer cross-attention to the encoder
memory; decode caches = self-attn KV + precomputed cross KV.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import blocks, common
from repro.models.config import ModelConfig, Runtime
from repro.parallel.sharding import Param, annotate, with_layer_axis

Params = dict[str, Any]


def init_encdec(key, cfg: ModelConfig) -> Params:
    assert cfg.n_encoder_layers > 0
    kk = jax.random.split(key, 6)

    def init_enc_layer(k):
        k1, k2 = jax.random.split(k)
        return {"attn": blocks.init_attn(k1, cfg), "ffn": blocks.init_mlp(k2, cfg)}

    def init_dec_layer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return {"self": blocks.init_attn(k1, cfg),
                "cross": blocks.init_attn(k2, cfg),
                "ffn": blocks.init_mlp(k3, cfg)}

    enc_keys = jax.random.split(kk[0], cfg.n_encoder_layers)
    dec_keys = jax.random.split(kk[1], cfg.n_layers)
    return {
        "embed": Param(common.trunc_normal(kk[2], (cfg.vocab_size, cfg.d_model),
                                           cfg.d_model ** -0.5, cfg.pdtype),
                       ("vocab", "embed")),
        "encoder": with_layer_axis(jax.vmap(init_enc_layer)(enc_keys)),
        "enc_norm": Param(jnp.ones((cfg.d_model,), cfg.pdtype), ("embed",)),
        "decoder": with_layer_axis(jax.vmap(init_dec_layer)(dec_keys)),
        "final_norm": Param(jnp.ones((cfg.d_model,), cfg.pdtype), ("embed",)),
    }


def encode(params: Params, cfg: ModelConfig, rt: Runtime, frames: jax.Array):
    """frames: [B,Se,D] precomputed frontend embeddings -> memory [B,Se,D]."""
    x = annotate(frames.astype(cfg.cdtype), "batch", "seq", None)
    b, se = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(se, dtype=jnp.int32)[None], (b, se))

    def body(x, lp):
        x, _ = blocks.attn_train(lp["attn"], x, cfg, rt, positions, causal=False)
        x = blocks.mlp_apply(lp["ffn"], x, cfg)
        return x, None

    body_fn = jax.checkpoint(body) if rt.remat else body
    x, _ = lax.scan(body_fn, x, params["encoder"])
    return common.rmsnorm(x, params["enc_norm"].value, cfg.norm_eps)


def decode_train(params: Params, cfg: ModelConfig, rt: Runtime, memory,
                 tokens: jax.Array):
    x = params["embed"].value.astype(cfg.cdtype)[tokens]
    x = annotate(x, "batch", "seq", None)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(x, lp):
        x, (k, v) = blocks.attn_train(lp["self"], x, cfg, rt, positions)
        x, (ck, cv) = blocks.attn_train(lp["cross"], x, cfg, rt, None, kv=memory)
        x = blocks.mlp_apply(lp["ffn"], x, cfg)
        return x, {"k": k.astype(cfg.cdtype), "v": v.astype(cfg.cdtype),
                   "ck": ck.astype(cfg.cdtype), "cv": cv.astype(cfg.cdtype)}

    body_fn = jax.checkpoint(body) if rt.remat else body
    x, caches = lax.scan(body_fn, x, params["decoder"])
    return common.rmsnorm(x, params["final_norm"].value, cfg.norm_eps), caches


def train_loss(params: Params, batch: dict, cfg: ModelConfig, rt: Runtime):
    memory = encode(params, cfg, rt, batch["frames"])
    h, _ = decode_train(params, cfg, rt, memory, batch["tokens"])
    xent = common.chunked_softmax_xent(h, params["embed"].value, batch["labels"],
                                       chunk=rt.xent_chunk)
    return xent, {"xent": xent}


def prefill(params: Params, cfg: ModelConfig, rt: Runtime, frames, tokens):
    """Encode + teacher-forced prompt pass; returns (logits, caches)."""
    memory = encode(params, cfg, rt, frames)
    h, caches = decode_train(params, cfg, rt, memory, tokens)
    logits = common.top1_logits(h[:, -1], params["embed"].value)
    return logits, caches


def init_cache(cfg: ModelConfig, batch: int, max_len: int, enc_len: int, dtype):
    kh, hd = cfg.n_kv_heads, cfg.hd
    one = {
        "k": jnp.zeros((batch, max_len, kh, hd), dtype),
        "v": jnp.zeros((batch, max_len, kh, hd), dtype),
        "ck": jnp.zeros((batch, enc_len, kh, hd), dtype),
        "cv": jnp.zeros((batch, enc_len, kh, hd), dtype),
    }
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros((cfg.n_layers,) + a.shape, a.dtype), one)


def decode_step(params: Params, cache: Params, tokens, pos, cfg: ModelConfig,
                rt: Runtime):
    """tokens: [B,1]; cache: stacked {k,v,ck,cv}."""
    x = params["embed"].value.astype(cfg.cdtype)[tokens]

    def body(x, xs):
        lp, lc = xs
        x, new_self = blocks.attn_decode(lp["self"], x, {"k": lc["k"], "v": lc["v"]},
                                         pos, cfg, rt)
        x = blocks.attn_cross_decode(lp["cross"], x, (lc["ck"], lc["cv"]), cfg)
        x = blocks.mlp_apply(lp["ffn"], x, cfg)
        return x, {"k": new_self["k"], "v": new_self["v"],
                   "ck": lc["ck"], "cv": lc["cv"]}

    x, new_cache = lax.scan(body, x, (params["decoder"], cache))
    h = common.rmsnorm(x, params["final_norm"].value, cfg.norm_eps)
    logits = common.top1_logits(h[:, 0], params["embed"].value)
    return logits, new_cache
