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
