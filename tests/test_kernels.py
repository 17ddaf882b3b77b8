"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def tol(dtype):
    return dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,sk,h,kh,d", [
    (1, 128, 128, 4, 4, 64),     # MHA square
    (2, 128, 128, 4, 2, 32),     # GQA
    (1, 64, 192, 6, 3, 16),      # sq != sk (prefix cache)
    (2, 256, 256, 8, 1, 64),     # MQA
])
def test_flash_attention(b, sq, sk, h, kh, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, sk, kh, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, sk, kh, d), jnp.float32).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out.astype(jnp.float32), want.astype(jnp.float32),
                               **tol(dtype))


def test_flash_attention_noncausal():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 32))
    k = jax.random.normal(ks[1], (2, 96, 2, 32))
    v = jax.random.normal(ks[2], (2, 96, 2, 32))
    out = ops.flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.ref_attention(q, k, v, causal=False)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,s,h,kh,d", [(2, 256, 8, 2, 64), (3, 128, 4, 4, 32),
                                        (1, 512, 2, 1, 128)])
def test_flash_decode(b, s, h, kh, d):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, s, kh, d))
    v = jax.random.normal(ks[2], (b, s, kh, d))
    kv_len = jnp.asarray([max(s - 13 * i, 1) for i in range(b)], jnp.int32)
    out = ops.flash_decode(q, k, v, kv_len, interpret=True)
    want = ref.ref_decode_attention(q, k, v, kv_len)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("rows,d", [(64, 128), (96, 256), (256, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(rows, d, dtype):
    x = jax.random.normal(KEY, (rows, d), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (d,), jnp.float32).astype(dtype)
    out = ops.rmsnorm(x, w, interpret=True)
    want = ref.ref_rmsnorm(x, w)
    np.testing.assert_allclose(out.astype(jnp.float32), want.astype(jnp.float32),
                               **tol(dtype))


@pytest.mark.parametrize("op", ["fma", "add", "mul", "rsqrt", "exp"])
def test_alu_chain(op):
    x = jax.random.uniform(KEY, (8, 128), jnp.float32) + 0.5
    a = jnp.full((8, 128), 0.5, jnp.float32)
    out = ops.alu_chain(x, a, n=8, op=op, interpret=True)
    if op == "fma":
        want = ref.ref_alu_chain(x, a, 8)
        np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
    assert bool(jnp.all(jnp.isfinite(out)))


@pytest.mark.parametrize("n,steps", [(32, 64), (128, 301)])
def test_chase(n, steps):
    rng = np.random.RandomState(3)
    idx = np.arange(n)
    rng.shuffle(idx)
    ring = np.empty(n, np.int32)
    ring[idx[:-1]] = idx[1:]
    ring[idx[-1]] = idx[0]
    out = ops.chase(jnp.asarray(ring), jnp.asarray([int(idx[0])]),
                    steps=steps, interpret=True)
    assert int(out[0]) == ref.ref_chase(ring, int(idx[0]), steps)


@pytest.mark.parametrize("b,s,dm,n,chunk", [(2, 64, 16, 8, 16), (1, 96, 8, 4, 32)])
def test_mamba_scan(b, s, dm, n, chunk):
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, s, dm)) * 0.5
    dt = jax.random.normal(ks[1], (b, s, dm)) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (dm, n)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, n)) * 0.5
    D = jax.random.normal(ks[5], (dm,)) * 0.1
    y = ops.mamba_scan(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    want, _ = ref.ref_selective_scan(x, dt, A, B, C, D)
    np.testing.assert_allclose(y, want, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("b,s,dm,n,chunk", [(2, 64, 16, 8, 16), (1, 96, 8, 4, 32)])
def test_mamba_scan_returns_its_final_state(b, s, dm, n, chunk):
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, s, dm)) * 0.5
    dt = jax.random.normal(ks[1], (b, s, dm)) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (dm, n)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, n)) * 0.5
    D = jax.random.normal(ks[5], (dm,)) * 0.1
    y, h = ops.mamba_scan(x, dt, A, B, C, D, chunk=chunk, interpret=True,
                          return_state=True)
    want_y, want_h = ref.ref_selective_scan(x, dt, A, B, C, D)
    np.testing.assert_allclose(y, want_y, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(h, want_h, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,d,f,e,tile", [(8, 64, 96, 4, 32), (32, 128, 256, 3, 128)])
def test_moe_experts(n, d, f, e, tile, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (n, d), jnp.float32).astype(dtype)
    comb = jax.random.uniform(ks[1], (n, e)) * (jax.random.uniform(
        ks[1], (n, e)) > 0.5)
    wg, wu = (jax.random.normal(k, (e, d, f), jnp.float32) * d ** -0.5
              for k in ks[2:4])
    wd = jax.random.normal(ks[4], (e, f, d), jnp.float32) * f ** -0.5
    wg, wu, wd = (w.astype(dtype) for w in (wg, wu, wd))
    out = ops.moe_experts(x, comb, wg, wu, wd, tile=tile, interpret=True)
    np.testing.assert_allclose(out, ref.ref_moe_experts(x, comb, wg, wu, wd),
                               **tol(dtype))


@pytest.mark.parametrize("periods,period,kh,h,lens,block_k", [
    (2, 1, 4, 32, (1, 16, 17, 64), 16),     # yi's G = 8: one, a block edge, max_len
    (1, 0, 8, 32, (64, 1, 33, 16), 16),     # jamba's G = 4, one period
    (3, 0, 4, 32, (1, 64, 31, 32), 32),
    (3, 2, 4, 32, (40,) * 4, 16),           # a scalar pos, broadcast to every row
], ids=["yi_period1", "jamba_period0", "yi_period0", "scalar_pos"])
def test_flash_decode_stacked(periods, period, kh, h, lens, block_k):
    """The stacked-cache kernel over period ``period`` equals the XLA decode
    attention over ``cache[period]``, each row up to its ``kv_len``."""
    from repro.models import common

    b, s, d = len(lens), 64, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (periods, b, s, kh, d),
                          jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (periods, b, s, kh, d),
                          jnp.float32).astype(jnp.bfloat16)
    kv_len = jnp.asarray(lens, jnp.int32)
    out = ops.flash_decode_stacked(q, k, v, jnp.int32(period), kv_len,
                                   block_k=block_k, interpret=True)
    want = common.decode_attention(q, k[period], v[period], kv_len)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32),
                               **tol(jnp.bfloat16))


def _attention_rounded_like_the_kernel(q, k, v, period, kv_len):
    """``common.decode_attention`` over ``cache[period]`` with the stacked
    kernel's rounding in one block: q.K of the stored bfloat16 values in
    float32, scaled after the dot, and P rounded to bfloat16 before P.V."""
    k = jax.lax.dynamic_index_in_dim(k, period, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(v, period, keepdims=False)
    b, h, d = q.shape
    s, kh = k.shape[1], k.shape[2]
    scores = jnp.einsum("bkgd,bskd->bkgs",
                        q.reshape(b, kh, h // kh, d).astype(jnp.float32),
                        k.astype(jnp.float32)) * d ** -0.5
    live = jnp.arange(s)[None, :] < kv_len[:, None]
    scores = jnp.where(live[:, None, None], scores, -1e30)
    p = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    out = jnp.einsum("bkgs,bskd->bkgd",
                     p.astype(v.dtype).astype(jnp.float32),
                     v.astype(jnp.float32)) / jnp.sum(p, -1, keepdims=True)
    return out.reshape(b, h, d).astype(q.dtype)


@pytest.mark.parametrize("per_row", [True, False], ids=["per_row", "scalar"])
def test_decode_step_through_the_stacked_kernel(monkeypatch, per_row):
    """``transformer.decode_step`` with its attention sent through the
    stacked-cache kernel (one block a row, so that its rounding can be
    reproduced) gives the logits and cache of the XLA step whose attention
    rounds as the kernel does: a slip in the kernel's period, row or
    ``kv_len`` indexing moves the logits by far more than the tolerance.
    The dispatch to the kernel on a TPU is checked where the step compiles
    for a described v5e (``tests/test_tpu_lowering.py``); the kernel's
    block clamping at block edges, by ``test_flash_decode_stacked``."""
    from repro.kernels.flash_decode import flash_decode_stacked
    from repro.models import common, transformer
    from repro.models.config import ModelConfig, Runtime

    cfg = ModelConfig(name="tiny", family="dense", n_layers=3, d_model=64,
                      n_heads=8, n_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=128, param_dtype="bfloat16",
                      compute_dtype="bfloat16", tie_embeddings=False)
    slots, max_len = 4, 64
    params = transformer.init_lm(KEY, cfg)
    cache = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.fold_in(KEY, a.ndim), a.shape,
                                    jnp.float32).astype(a.dtype),
        transformer.init_cache(cfg, slots, max_len, cfg.cdtype))
    tokens = jnp.arange(slots, dtype=jnp.int32)[:, None]
    pos = jnp.asarray([0, 15, 16, 63], jnp.int32) if per_row else jnp.int32(31)

    def step(attention):
        monkeypatch.setattr(common, "decode_attention_stacked", attention)
        return jax.jit(lambda p, c, t, q: transformer.decode_step(
            p, c, t, q, cfg, Runtime()))(params, cache, tokens, pos)

    want_logits, want_cache = step(_attention_rounded_like_the_kernel)
    logits, new_cache = step(
        lambda q, k, v, period, kv_len: flash_decode_stacked(
            q, k, v, period, kv_len, block_k=max_len, interpret=True))
    scale = float(jnp.max(jnp.abs(want_logits)))
    np.testing.assert_allclose(logits, want_logits, rtol=0, atol=1e-3 * scale)
    for got, want in zip(jax.tree_util.tree_leaves(new_cache),
                         jax.tree_util.tree_leaves(want_cache)):
        np.testing.assert_allclose(got.astype(jnp.float32),
                                   want.astype(jnp.float32),
                                   rtol=1e-2, atol=1e-2)
