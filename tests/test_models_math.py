"""Model math: attention impl equivalence, rope/mrope, moe routing, ssm/xlstm
recurrent vs chunked equivalence."""
import dataclasses
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import blocks, common, ssm, xlstm
from repro.models.config import ModelConfig, Runtime
from repro.parallel.sharding import unbox

KEY = jax.random.PRNGKey(3)


# ----------------------------------------------------------------- attention
@pytest.mark.parametrize("sq,sk,block", [(64, 64, 16), (32, 96, 32), (128, 128, 128)])
def test_blockwise_matches_plain(sq, sk, block):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, sq, 4, 32))
    k = jax.random.normal(ks[1], (2, sk, 2, 32))
    v = jax.random.normal(ks[2], (2, sk, 2, 32))
    a = common.plain_attention(q, k, v, causal=True, q_offset=sk - sq)
    b = common.blockwise_attention(q, k, v, causal=True, q_offset=sk - sq,
                                   block_k=block)
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def _program(fn, *args) -> str:
    """``fn``'s program compiled here, without its module's name, metadata
    and source tables, and with its names numbered in order of appearance."""
    text = jax.jit(fn).lower(*args).compile().as_text().split("\nFileNames")[0]
    text = re.sub(r"^HloModule \S+", "HloModule", text)
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(), f"%{len(names)}"), text)


def _qkv(s, h=4, kh=2, d=32):
    ks = jax.random.split(KEY, 3)
    return tuple(jax.random.normal(key, (1, s, n, d))
                 for key, n in zip(ks, (h, kh, kh)))


@pytest.mark.parametrize("s,xla", [(64, "plain"), (4096, "blockwise")])
def test_auto_attention_off_tpu_is_the_xla_path(s, xla):
    """Off a TPU, ``auto`` causal self-attention computes and compiles to
    exactly what the XLA path it chose by length does, the kernel's branch
    being dropped when the program is lowered for this platform."""
    q, k, v = _qkv(s, kh=1, d=8) if s >= 4096 else _qkv(s)

    def auto(q, k, v):
        return common.attention(q, k, v)

    def explicit(q, k, v):
        return common.attention(q, k, v, impl=xla)

    np.testing.assert_array_equal(auto(q, k, v), explicit(q, k, v))
    assert _program(auto, q, k, v) == _program(explicit, q, k, v)


def test_auto_attention_under_a_mesh_is_plain():
    """Under an active sharding mesh ``auto`` never stages the kernel, so
    it lowers as ``plain``."""
    from jax.sharding import Mesh

    from repro.parallel import sharding

    q, k, v = _qkv(64)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    with sharding.use_sharding(mesh, sharding.lm_rules()):
        auto = _program(lambda q, k, v: common.attention(q, k, v), q, k, v)
        plain = _program(lambda q, k, v: common.attention(q, k, v, impl="plain"),
                         q, k, v)
    assert auto == plain


def test_flash_prefill_counter_counts_kernel_selections():
    """``attn.flash_prefill`` counts each trace in which ``auto`` stages the
    kernel: causal self-attention with no offset and no mesh, only."""
    from jax.sharding import Mesh

    from repro import tracing
    from repro.parallel import sharding

    q, k, v = _qkv(64)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))

    def selections(run) -> int:
        tracing.collect()
        run()
        return sum(c.n for c in tracing.collect()[1]
                   if c.name == "attn.flash_prefill")

    tracing.enable()
    try:
        assert selections(lambda: common.attention(q, k, v)) == 1
        assert selections(lambda: common.attention(q, k, v, causal=False)) == 0
        assert selections(lambda: common.attention(q[:, :32], k, v)) == 0
        assert selections(lambda: common.attention(q, k, v, q_offset=8)) == 0
        for impl in ("plain", "blockwise", "pallas"):
            assert selections(lambda: common.attention(q, k, v, impl=impl)) == 0
        with sharding.use_sharding(mesh, sharding.lm_rules()):
            assert selections(lambda: common.attention(q, k, v)) == 0
        jitted = jax.jit(lambda q, k, v: common.attention(q, k, v))
        assert selections(lambda: [jitted(q, k, v) for _ in range(3)]) == 1
    finally:
        tracing.disable()


def test_decode_attention_matches_plain_lastrow():
    ks = jax.random.split(KEY, 3)
    s = 64
    q = jax.random.normal(ks[0], (2, s, 4, 32))
    k = jax.random.normal(ks[1], (2, s, 2, 32))
    v = jax.random.normal(ks[2], (2, s, 2, 32))
    full = common.plain_attention(q, k, v, causal=True)
    dec = common.decode_attention(q[:, -1], k, v, kv_len=s)
    np.testing.assert_allclose(full[:, -1], dec, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------- rope
def test_rope_relative_position_invariance():
    """RoPE: <q_i, k_j> depends only on i-j."""
    d = 32
    q = jax.random.normal(KEY, (1, 1, 1, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 1, 1, d))
    def dot_at(i, j):
        qi = common.apply_rope(q, jnp.asarray([[i]]), 1e4)
        kj = common.apply_rope(k, jnp.asarray([[j]]), 1e4)
        return float(jnp.sum(qi * kj))
    assert dot_at(5, 3) == pytest.approx(dot_at(105, 103), abs=1e-3)
    assert dot_at(5, 3) != pytest.approx(dot_at(5, 4), abs=1e-4)


def test_mrope_equals_rope_when_streams_equal():
    """With t==h==w positions, M-RoPE must reduce to 1-D RoPE."""
    d = 32
    x = jax.random.normal(KEY, (2, 8, 3, d))
    pos1 = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    pos3 = jnp.broadcast_to(pos1[None], (3, 2, 8))
    a = common.apply_rope(x, pos1, 1e4)
    b = common.apply_mrope(x, pos3, (4, 6, 6), 1e4)
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# ----------------------------------------------------------------------- moe
def test_moe_dispatch_slots_unique_and_capacity():
    idx = jnp.asarray([[0, 0, 0, 1, 1, 2, 3, 3]])
    slot = blocks._dispatch_indices(idx, n_experts=4, capacity=2)
    slots = np.asarray(slot)[0]
    kept = slots[slots < 8]
    assert len(set(kept.tolist())) == len(kept)          # unique slots
    assert (slots[:2] == [0, 1]).all()                   # first two of e0 kept
    assert slots[2] == 8                                 # third dropped


def test_moe_fully_routes_with_high_capacity():
    cfg = ModelConfig(name="m", family="moe", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=64,
                      period=(("attn", "moe"),), n_experts=4, top_k=2,
                      capacity_factor=8.0, param_dtype="float32",
                      compute_dtype="float32")
    p = blocks.init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (2, 8, 32))
    rt = Runtime(moe_groups=1)
    out, aux = blocks.moe_apply(p, x, cfg, rt)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(aux))
    # top-2 output == weighted sum of the two chosen experts, computed densely
    h = common.rmsnorm(x, p["norm"].value)
    logits = jnp.einsum("bsd,de->bse", h, p["router"].value)
    gates = jax.nn.softmax(logits, -1)
    w, e = jax.lax.top_k(gates, 2)
    w = w / w.sum(-1, keepdims=True)
    def expert(i, xin):
        g = jax.nn.silu(xin @ p["wg"].value[i]) * (xin @ p["wu"].value[i])
        return g @ p["wd"].value[i]
    dense = jnp.stack([expert(i, h) for i in range(4)], axis=2)  # [B,S,E,D]
    want = jnp.einsum("bsk,bskd->bsd", w,
                      jnp.take_along_axis(dense, e[..., None], axis=2))
    np.testing.assert_allclose(np.asarray(out - x), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------------------- ssm
def test_mamba_chunked_equals_reference_scan():
    from repro.kernels import ref as kref
    b, s, di, n = 2, 32, 8, 4
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, s, di)) * 0.5
    dt_raw = jax.random.normal(ks[1], (b, s, di)) * 0.1
    a = -jnp.exp(jax.random.normal(ks[2], (di, n)) * 0.3)
    bb = jax.random.normal(ks[3], (b, s, n)) * 0.5
    cc = jax.random.normal(ks[4], (b, s, n)) * 0.5
    dt = jax.nn.softplus(dt_raw)
    da = jnp.exp(dt[..., None] * a[None, None])
    y, hf = ssm._chunk_scan(dt, a, bb, cc, x, chunk=8)
    # sequential oracle
    want, href = kref.ref_selective_scan(x, dt_raw, a, bb, cc,
                                         jnp.zeros((di,)))
    np.testing.assert_allclose(y, want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(hf, href, atol=1e-4, rtol=1e-4)


def test_mamba_train_decode_state_consistency():
    cfg = ModelConfig(name="m", family="hybrid", n_layers=1, d_model=16,
                      n_heads=2, n_kv_heads=2, d_ff=32, vocab_size=64,
                      period=(("mamba", "none"),), ssm_state=4, ssm_conv=4,
                      ssm_expand=2, param_dtype="float32", compute_dtype="float32")
    p = ssm.init_mamba(KEY, cfg)
    rt = Runtime(mamba_chunk=4)
    x = jax.random.normal(KEY, (1, 12, 16)) * 0.5
    y_full, cache = ssm.mamba_train(p, x, cfg, rt)
    # replay last token with decode from the cache of the first 11
    y_pre, cache_pre = ssm.mamba_train(p, x[:, :11], cfg, rt)
    y_dec, _ = ssm.mamba_decode(p, x[:, 11:12], cache_pre, cfg)
    np.testing.assert_allclose(np.asarray(y_dec[:, 0]),
                               np.asarray(y_full[:, 11]), atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------- xlstm
def test_mlstm_chunked_equals_recurrent():
    """Chunked training path vs the exact stabilized decode recurrence."""
    b, s, nh, dh = 1, 16, 2, 8
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (b, s, nh, dh)) * 0.3
    k = jax.random.normal(ks[1], (b, s, nh, dh)) * 0.3
    v = jax.random.normal(ks[2], (b, s, nh, dh)) * 0.5
    ig = jax.random.normal(ks[3], (b, s, nh)) * 0.5 - 1.0
    fg = jax.random.normal(ks[4], (b, s, nh)) * 0.5 + 2.0
    h_chunk, _ = xlstm._mlstm_chunked(q, k, v, ig, fg, chunk=4)
    # recurrent oracle (unstabilized, f32, same normalizer)
    logf = jax.nn.log_sigmoid(fg)
    c = jnp.zeros((b, nh, dh, dh))
    n = jnp.zeros((b, nh, dh))
    outs = []
    scale = dh ** -0.5
    for t in range(s):
        f_t = jnp.exp(logf[:, t])[..., None]
        i_t = jnp.exp(ig[:, t])[..., None]
        c = f_t[..., None] * c + i_t[..., None] * k[:, t][..., None] * v[:, t][..., None, :]
        n = f_t * n + i_t * k[:, t]
        num = jnp.einsum("bhd,bhde->bhe", q[:, t] * scale, c)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q[:, t] * scale, n)), 1.0)
        outs.append(num / den[..., None])
    want = jnp.stack(outs, 1).reshape(b, s, nh * dh)
    np.testing.assert_allclose(h_chunk, want, atol=1e-4, rtol=1e-4)


def test_slstm_decode_matches_train():
    cfg = ModelConfig(name="x", family="ssm", n_layers=1, d_model=16,
                      n_heads=2, n_kv_heads=2, d_ff=0, vocab_size=64,
                      period=(("slstm", "none"),), param_dtype="float32",
                      compute_dtype="float32")
    p = xlstm.init_slstm(KEY, cfg)
    rt = Runtime()
    x = jax.random.normal(KEY, (2, 9, 16)) * 0.5
    y_full, _ = xlstm.slstm_train(p, x, cfg, rt)
    _, cache = xlstm.slstm_train(p, x[:, :8], cfg, rt)
    y_dec, _ = xlstm.slstm_decode(p, x[:, 8:9], cache, cfg)
    np.testing.assert_allclose(np.asarray(y_dec[:, 0]), np.asarray(y_full[:, 8]),
                               atol=1e-5, rtol=1e-5)


# ------------------------------------------------- decode step, stacked cache
def _decode_by_period(params, cache, tokens, pos, cfg, rt):
    """The decode step as a plain loop: slice each period's parameters and
    cache, run its layers with the per-layer decode functions, stack."""
    from repro.models import transformer

    x = params["embed"].value.astype(cfg.cdtype)[tokens]
    caches = []
    for j in range(cfg.n_periods):
        pp = jax.tree_util.tree_map(lambda a: a[j], params["periods"])
        pc = jax.tree_util.tree_map(lambda a: a[j], cache)
        new = {}
        for i, (mixer, ffn) in enumerate(cfg.period):
            p, c = pp[f"l{i}"], pc[f"l{i}"]
            if mixer == "attn":
                x, c = blocks.attn_decode(p["mixer"], x, c, pos, cfg, rt)
            elif mixer == "mamba":
                x, c = ssm.mamba_decode(p["mixer"], x, c, cfg)
            elif mixer == "mlstm":
                x, c = xlstm.mlstm_decode(p["mixer"], x, c, cfg)
            elif mixer == "slstm":
                x, c = xlstm.slstm_decode(p["mixer"], x, c, cfg)
            if ffn == "dense":
                x = blocks.mlp_apply(p["ffn"], x, cfg)
            new[f"l{i}"] = c
        caches.append(new)
    h = common.rmsnorm(x, params["final_norm"].value)
    logits = common.top1_logits(h[:, 0],
                                transformer._out_embed(params, cfg))
    return logits, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *caches)


def _decode_configs():
    from repro.api.probes import serving_tiny_config

    tiny, _ = serving_tiny_config()
    common_kw = dict(n_layers=4, d_model=32, n_heads=4, n_kv_heads=2,
                     d_ff=64, vocab_size=128, param_dtype="float32",
                     compute_dtype="float32")
    return {
        "attn": tiny,
        "attn+mamba": ModelConfig(name="hybrid", family="hybrid",
                                  period=(("attn", "dense"),
                                          ("mamba", "dense")), **common_kw),
        "mlstm+slstm": ModelConfig(name="xlstm", family="ssm",
                                   period=(("mlstm", "none"),
                                           ("slstm", "none")), **common_kw),
    }


@pytest.mark.parametrize("per_row", [False, True], ids=["scalar", "per_row"])
@pytest.mark.parametrize("arch", ["attn", "attn+mamba", "mlstm+slstm"])
def test_decode_step_matches_per_period_loop(arch, per_row):
    """The stacked cache carried through the scan and written in place gives
    the logits and cache of a loop that slices each period out and stacks
    the results, bit for bit, over consecutive steps; per-row positions
    include a row at 0 and a row that ends at ``max_len - 1``."""
    from repro.models import transformer

    cfg = _decode_configs()[arch]
    rt = Runtime(remat=False, xent_chunk=16, moe_groups=1)
    b, max_len, steps = 4, 16, 3
    params = transformer.init_lm(KEY, cfg)
    cache = transformer.init_cache(cfg, b, max_len, cfg.cdtype)
    leaves, tree = jax.tree_util.tree_flatten(cache)
    keys = jax.random.split(jax.random.PRNGKey(11), len(leaves))
    cache = jax.tree_util.tree_unflatten(tree, [
        (jax.random.normal(k, a.shape) * 0.5).astype(a.dtype)
        for k, a in zip(keys, leaves)])
    start = (np.array([0, 3, 7, max_len - steps], np.int32) if per_row
             else max_len - steps)
    tokens = jax.random.randint(jax.random.PRNGKey(12), (steps, b, 1), 0,
                                cfg.vocab_size)
    step = jax.jit(lambda p, c, t, pos: transformer.decode_step(
        p, c, t, pos, cfg, rt))
    ref = jax.jit(lambda p, c, t, pos: _decode_by_period(p, c, t, pos, cfg,
                                                         rt))
    got_c, want_c = cache, cache
    for t in range(steps):
        pos = jnp.asarray(start + t, jnp.int32)
        got, got_c = step(params, got_c, tokens[t], pos)
        want, want_c = ref(params, want_c, tokens[t], pos)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for g, w in zip(jax.tree_util.tree_leaves(got_c),
                        jax.tree_util.tree_leaves(want_c)):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------------------------------------------ runtime options
@pytest.mark.parametrize("field", [f.name for f in dataclasses.fields(Runtime)])
def test_runtime_field_is_read(field):
    """Every ``Runtime`` option selects something: ``rt.<field>`` is read
    somewhere in the package outside its definition."""
    import repro

    root = pathlib.Path(repro.__file__).parent
    pattern = re.compile(rf"\brt\.{field}\b")
    readers = [path for path in root.rglob("*.py")
               if path != root / "models" / "config.py"
               and pattern.search(path.read_text())]
    assert readers, f"Runtime.{field} is read by no code"
