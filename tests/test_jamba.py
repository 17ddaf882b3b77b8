"""Jamba through the serving path against the plain float32 reference
(``bench/models/jamba.py``) on seeded random weights, at a tiny size on the
CPU, comparing logits; and the mechanisms it forced: a dropless expert
layer that holds a share of the experts, Mamba's dt/B/C norms and its state
from prefill into decode, attention without positional encoding, the
published layer order and the configured norm epsilon."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.adapters import jamba as adapter
from bench.models import jamba as ref
from repro.configs.registry import get
from repro.models import blocks, common, encdec, ssm, transformer
from repro.models.config import ModelConfig, Runtime
from repro.serving import Engine

KEY = jax.random.PRNGKey(7)
# A Jamba period at tiny widths, in float32 so that the program and the
# reference differ only in the order of their sums.
TINY = dict(
    name="tiny-jamba", family="jamba", attn_layer_offset=4,
    attn_layer_period=8, expert_layer_offset=1, expert_layer_period=2,
    hidden_size=64, intermediate_size=96, mamba_conv_bias=True,
    mamba_d_conv=4, mamba_d_state=8, mamba_dt_rank=8, mamba_expand=2,
    mamba_proj_bias=False, num_attention_heads=4, num_key_value_heads=2,
    num_experts=8, num_experts_per_tok=2, num_hidden_layers=8,
    rms_norm_eps=1e-6, tie_word_embeddings=False, vocab_size=256,
    torch_dtype="float32", router_experts=16, experts_held=[0, 8])
# float32 on both sides at the same widths: only the order of summation
# differs, a few float32 ulps of logits of order 1
TOL = dict(atol=2e-4, rtol=2e-4)


def _moe_cfg(**kw) -> ModelConfig:
    base = dict(name="moe", family="moe", n_layers=1, d_model=32, n_heads=4,
                n_kv_heads=2, d_ff=48, vocab_size=64,
                period=(("attn", "moe"),), n_experts=16, top_k=2,
                moe_dropless=True, moe_renormalize=False,
                param_dtype="float32", compute_dtype="float32")
    return ModelConfig(**{**base, **kw})


def _share(p, lo, hi):
    """The layer's parameters as a chip holding experts ``[lo, hi)`` has
    them: the whole router, its experts' weights."""
    return {**p, **{w: dataclasses.replace(p[w], value=p[w].value[lo:hi])
                    for w in ("wg", "wu", "wd")}}


def _dense_moe(p, x, cfg):
    """Every token through its top-k experts, one at a time, by hand."""
    h = common.rmsnorm(x, p["norm"].value, cfg.norm_eps)
    gates = jax.nn.softmax(h @ p["router"].value, -1)
    w, e = jax.lax.top_k(gates, cfg.top_k)

    def expert(i, xin):
        g = jax.nn.silu(xin @ p["wg"].value[i]) * (xin @ p["wu"].value[i])
        return g @ p["wd"].value[i]

    outs = jnp.stack([expert(i, h) for i in range(cfg.n_experts)], axis=2)
    return jnp.einsum("bsk,bskd->bsd", w,
                      jnp.take_along_axis(outs, e[..., None], axis=2))


@pytest.fixture(scope="module")
def program():
    w = ref.init_weights(5, TINY)
    params, cfg, rt = adapter.program(TINY, w)
    return w, Engine(params, cfg, rt, max_len=128)


def test_pool_serves_what_the_reference_computes(program):
    """Prefill, then decode through a busy ``SlotPool``: three requests
    admitted at different times into three slots, every logit the program
    produced against the reference's full forward over that request."""
    w, eng = program
    seen = []
    prefill, decode = eng._prefill, eng._decode

    def rec_prefill(*a):
        out = prefill(*a)
        seen.append(("prefill", np.asarray(out[0])))
        return out

    def rec_decode(*a):
        out = decode(*a)
        seen.append(("decode", np.asarray(out[0])))
        return out

    eng._prefill, eng._decode = rec_prefill, rec_decode
    try:
        pool = eng.slots(3)
        rng = np.random.default_rng(1)
        prompts = {0: rng.integers(1, 256, 70).tolist(),
                   1: rng.integers(1, 256, 33).tolist(),
                   2: rng.integers(1, 256, 5).tolist()}
        logits = {u: [] for u in prompts}
        served = {u: [] for u in prompts}

        def admit(uid):
            served[uid].append(pool.admit(uid, prompts[uid], uid=uid,
                                          max_new=20))
            logits[uid].append(seen[-1][1][0])

        def step():
            toks = pool.step()
            for uid in pool.active_slots():
                served[uid].append(int(toks[uid]))
                logits[uid].append(seen[-1][1][uid])

        admit(0)
        step()
        step()
        admit(1)
        step()
        admit(2)
        for _ in range(3):
            step()
    finally:
        eng._prefill, eng._decode = prefill, decode
    for uid, prompt in prompts.items():
        seq = prompt + served[uid][:-1]
        tokens = np.zeros(128, np.int32)
        tokens[:len(seq)] = seq
        rows = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
        want = ref.logits(w, TINY, jnp.asarray(tokens), jnp.asarray(rows))
        np.testing.assert_allclose(np.stack(logits[uid]), np.asarray(want),
                                   **TOL)


@pytest.mark.parametrize("tokens", [24, 96])
def test_expert_shares_sum_to_the_uncut_layer(tokens):
    """Experts 0-8 and 8-16, each computed alone with the whole router, add
    up to the layer that holds all 16 (at 24 tokens every token goes
    through every held expert; at 96 the grouped matmul runs)."""
    whole = _moe_cfg()
    p = blocks.init_moe(KEY, whole)
    x = jax.random.normal(KEY, (2, tokens // 2, 32))
    rt = Runtime()
    full, _ = blocks.moe_apply(p, x, whole, rt)
    parts = [blocks.moe_apply(_share(p, lo, hi), x,
                              _moe_cfg(experts_held=(lo, hi)), rt)[0] - x
             for lo, hi in ((0, 8), (8, 16))]
    np.testing.assert_allclose(np.asarray(x + parts[0] + parts[1]),
                               np.asarray(full), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(full - x),
                               np.asarray(_dense_moe(p, x, whole)),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("tokens", [32, 128])
def test_skewed_router_drops_no_token(tokens):
    """A router that sends nearly every token to expert 3: the dropless
    layer computes all of them (the capacity layer, at the same skew,
    drops most)."""
    cfg = _moe_cfg()
    p = blocks.init_moe(KEY, cfg)
    x = 0.1 * jax.random.normal(KEY, (1, tokens, 32)) + 1.0
    router = p["router"].value.at[:, 3].set(5.0)
    p = {**p, "router": dataclasses.replace(p["router"], value=router)}
    h = common.rmsnorm(x, p["norm"].value, cfg.norm_eps)
    _, top_e = jax.lax.top_k(h @ router, 2)
    assert float(jnp.mean(top_e[..., 0] == 3)) > 0.9
    out, _ = blocks.moe_apply(p, x, cfg, Runtime())
    want = _dense_moe(p, x, cfg)
    np.testing.assert_allclose(np.asarray(out - x), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    capped, _ = blocks.moe_apply(
        p, x, dataclasses.replace(cfg, moe_dropless=False,
                                  moe_renormalize=False), Runtime())
    assert not np.allclose(np.asarray(capped - x), np.asarray(want),
                           atol=1e-3)


def test_router_renormalizes_only_where_configured():
    cfg = _moe_cfg()
    p = blocks.init_moe(KEY, cfg)
    h = jax.random.normal(KEY, (5, 32))
    _, w_raw, _ = blocks._route(p, h, cfg)
    _, w_norm, _ = blocks._route(p, h, dataclasses.replace(
        cfg, moe_renormalize=True))
    assert np.all(np.asarray(w_raw.sum(-1)) < 1.0)
    np.testing.assert_allclose(np.asarray(w_norm.sum(-1)), 1.0, rtol=1e-6)


def _mamba_cfg(**kw):
    return ModelConfig(name="m", family="hybrid", n_layers=1, d_model=16,
                       n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=64,
                       period=(("mamba", "none"),), ssm_state=4, ssm_conv=4,
                       ssm_expand=2, ssm_dbc_norm=True, norm_eps=1e-6,
                       param_dtype="float32", compute_dtype="float32", **kw)


def test_mamba_state_after_prefill_continues_into_decode():
    """With Jamba's dt/B/C norms: the prefill's state and convolution tail,
    carried through five decode steps, give the full sequence's outputs
    and its final state."""
    cfg = _mamba_cfg()
    p = ssm.init_mamba(KEY, cfg)
    p = {**p, **{n: dataclasses.replace(p[n], value=1.0 + 0.3 *
                                        jax.random.normal(KEY, p[n].value.shape))
                 for n in ("dt_norm", "b_norm", "c_norm")}}
    rt = Runtime(mamba_chunk=4)
    x = jax.random.normal(KEY, (2, 21, 16)) * 0.5
    y_full, c_full = ssm.mamba_train(p, x, cfg, rt)
    _, cache = ssm.mamba_train(p, x[:, :16], cfg, rt)
    for t in range(16, 21):
        y_t, cache = ssm.mamba_decode(p, x[:, t:t + 1], cache, cfg)
        np.testing.assert_allclose(np.asarray(y_t[:, 0]),
                                   np.asarray(y_full[:, t]), **TOL)
    np.testing.assert_allclose(np.asarray(cache["h"]),
                               np.asarray(c_full["h"]), **TOL)


def test_dbc_norms_change_the_mixer():
    cfg = _mamba_cfg()
    p = ssm.init_mamba(KEY, cfg)
    p = {**p, "dt_norm": dataclasses.replace(p["dt_norm"],
                                              value=p["dt_norm"].value * 3)}
    x = jax.random.normal(KEY, (1, 8, 16))
    with_norms, _ = ssm.mamba_train(p, x, cfg, Runtime(mamba_chunk=4))
    without, _ = ssm.mamba_train(p, x, dataclasses.replace(
        cfg, ssm_dbc_norm=False), Runtime(mamba_chunk=4))
    assert not np.allclose(np.asarray(with_norms), np.asarray(without))


def test_pallas_prefill_returns_the_final_state():
    """The Pallas scan's prefill hands decode the state it ended in, the
    same as the chunked scan's (not zeros)."""
    cfg = _mamba_cfg()
    p = ssm.init_mamba(KEY, cfg)
    x = jax.random.normal(KEY, (2, 16, 16)) * 0.5
    _, want = ssm.mamba_train(p, x, cfg, Runtime(mamba_chunk=8))
    _, got = ssm.mamba_train(p, x, cfg, Runtime(mamba_chunk=8,
                                                use_pallas=True))
    assert float(jnp.abs(want["h"]).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(got["h"]), np.asarray(want["h"]),
                               atol=1e-4, rtol=1e-4)


def test_period_is_in_published_order():
    """Attention at index 4 of each period of 8, MoE on the odd layers,
    from the registry and from the published offsets alike."""
    want = (("mamba", "dense"), ("mamba", "moe"), ("mamba", "dense"),
            ("mamba", "moe"), ("attn", "dense"), ("mamba", "moe"),
            ("mamba", "dense"), ("mamba", "moe"))
    spec = get("jamba-v0.1-52b")
    assert spec.config.period == want
    assert spec.smoke.period == want
    assert tuple(ref.layout(TINY)) == want
    assert adapter.model_config(TINY).period == want


@pytest.mark.parametrize("pos_emb", ["none", "rope"])
def test_positional_encoding_is_applied_only_when_configured(pos_emb):
    """Spreading the positions apart (RoPE sees only their differences)
    moves RoPE attention and leaves attention without positional encoding
    as it was."""
    cfg = _moe_cfg(period=(("attn", "dense"),), moe_dropless=False,
                   pos_emb=pos_emb)
    p = blocks.init_attn(KEY, cfg)
    x = jax.random.normal(KEY, (1, 12, 32))
    pos = jnp.arange(12)[None]
    rt = Runtime(attn_impl="plain")
    a, _ = blocks.attn_train(p, x, cfg, rt, pos)
    b, _ = blocks.attn_train(p, x, cfg, rt, pos * 3)
    same = np.allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert same == (pos_emb == "none")


def _hand_rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def test_mlp_takes_the_configured_norm_epsilon():
    """At eps 1e-2 and inputs of rms 0.05, the norm differs from one at
    1e-6 by a factor of about 2: the block must use the configured one."""
    cfg = _moe_cfg(period=(("attn", "dense"),), moe_dropless=False,
                   norm_eps=1e-2)
    p = blocks.init_mlp(KEY, cfg)
    x = 0.05 * jax.random.normal(KEY, (1, 6, 32))
    h = _hand_rms(x, p["norm"].value, 1e-2)
    g = jax.nn.silu(h @ p["wg"].value) * (h @ p["wu"].value)
    np.testing.assert_allclose(np.asarray(blocks.mlp_apply(p, x, cfg)),
                               np.asarray(x + g @ p["wd"].value),
                               atol=1e-5, rtol=1e-5)


def _smoke(arch, eps):
    return dataclasses.replace(get(arch).smoke, norm_eps=eps,
                               param_dtype="float32",
                               compute_dtype="float32")


@pytest.mark.parametrize("arch", ["jamba-v0.1-52b", "xlstm-350m",
                                  "granite-3-8b", "seamless-m4t-large-v2"])
def test_every_norm_takes_the_configured_epsilon(arch, monkeypatch):
    """Every RMSNorm a forward and a decode step call gets the
    configuration's epsilon (the default, 1e-6, is not it)."""
    eps_seen = []
    norm = common.rmsnorm

    def spy(x, w, eps=1e-6):
        eps_seen.append(eps)
        return norm(x, w, eps)

    monkeypatch.setattr(common, "rmsnorm", spy)
    cfg = _smoke(arch, 1e-2)
    rt = Runtime(mamba_chunk=8, mlstm_chunk=8, remat=False)
    tokens = jax.random.randint(KEY, (1, 16), 0, cfg.vocab_size)
    if cfg.n_encoder_layers:
        params = encdec.init_encdec(KEY, cfg)
        frames = jax.random.normal(KEY, (1, 8, cfg.d_model))
        encdec.decode_train(params, cfg, rt,
                            encdec.encode(params, cfg, rt, frames), tokens)
    else:
        params = transformer.init_lm(KEY, cfg)
        _, cache = transformer.prefill(params, cfg, rt, tokens=tokens)
        cache = transformer.pad_cache(cache, cfg, 17)
        transformer.decode_step(params, cache, tokens[:, -1:], 16, cfg, rt)
    assert eps_seen and set(eps_seen) == {1e-2}


def _decode_text(eng, slots=2):
    cache = transformer.init_cache(eng.cfg, slots, eng.max_len, eng.cfg.cdtype)
    lowered = eng._decode.lower(eng.params, cache,
                                jnp.zeros((slots, 1), jnp.int32),
                                jnp.zeros((slots,), jnp.int32))
    return lowered.compile().as_text()


def _program_only(text):
    """HLO instructions without their metadata and source locations."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return [ln for ln in text.splitlines()
            if " = " in ln or ln.startswith(("ENTRY", "%", "}"))]


@pytest.mark.parametrize("scope", ["mamba", "moe", "router", "experts",
                                   "attn", "mlp"])
def test_decode_op_names_carry_the_hybrid_scopes(program, scope):
    _, eng = program
    op_names = re.findall(r'op_name="([^"]*)"', _decode_text(eng))
    assert any(scope in n.split("/") for n in op_names)


def test_scopes_change_no_compiled_program(program, monkeypatch):
    """Named scopes only annotate: without them the decode step compiles
    to the same instructions."""
    _, eng = program
    scoped = _program_only(_decode_text(eng))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = Engine(eng.params, eng.cfg, eng.rt, max_len=eng.max_len)
    assert _program_only(_decode_text(bare)) == scoped
