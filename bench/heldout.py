"""The held-out programs the characterization cell's predictions are scored on.

Three plain ``jax.numpy`` programs, fixed for good: a change to them would
change what ``pred_accuracy`` means. None of them is part of the program,
so no serving change moves them; only the probes, chains and estimator do.

* ``decode_layer``: one decoder layer's decode step at InternLM2-20B widths
  (d 6144, 48/8 heads of 128, MLP 16384) for 48 rows over a 4,096-position
  bf16 cache: memory-bound.
* ``prefill_layer``: the same layer over 2,048 tokens, causal: bound by
  its matmuls.
* ``small_step``: 20 argument buffers of one vreg each and a few
  elementwise operations: bound by dispatch.

Inputs come from the run's seed. Each program computes in bf16 with float32
accumulation and returns float32; :func:`reference` computes it in float32
at ``highest`` precision, and the benchmark compares the two.
:func:`program` with ``dtype`` set computes the matmuls in another type (the
control's lower precision).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

D, H, KH, HD, F = 6144, 48, 8, 128, 16384
ROWS, CACHE, PREFILL = 48, 4096, 2048
SMALL_ARGS = 20
NAMES = ("decode_layer", "prefill_layer", "small_step")


def _rms(x):
    xf = x.astype(jnp.float32)
    return (xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6))


def _dot(spec, a, b, dtype, precision):
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32, precision=precision)


def _mlp(h, w, dtype, precision):
    g = _dot("td,df->tf", h, w["wg"], dtype, precision)
    u = _dot("td,df->tf", h, w["wu"], dtype, precision)
    return _dot("tf,fd->td", jax.nn.silu(g) * u, w["wd"], dtype, precision)


def _decode(x, kc, vc, kv_len, w, dtype=jnp.bfloat16, precision=None):
    h = _rms(x)
    q = _dot("td,dk->tk", h, w["wq"], dtype, precision).reshape(ROWS, KH, H // KH, HD)
    s = _dot("bkgd,bskd->bkgs", q, kc, dtype, precision) * HD ** -0.5
    valid = jnp.arange(CACHE)[None, :] < kv_len[:, None]
    s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = _dot("bkgs,bskd->bkgd", p, vc, dtype, precision).reshape(ROWS, D)
    x = x.astype(jnp.float32) + _dot("td,de->te", o, w["wo"], dtype, precision)
    return x + _mlp(_rms(x), w, dtype, precision)


def _prefill(x, w, dtype=jnp.bfloat16, precision=None):
    h = _rms(x)
    q = _dot("td,dk->tk", h, w["wq"], dtype, precision).reshape(PREFILL, KH, H // KH, HD)
    k = _dot("td,dk->tk", h, w["wk"], dtype, precision).reshape(PREFILL, KH, HD)
    v = _dot("td,dk->tk", h, w["wv"], dtype, precision).reshape(PREFILL, KH, HD)
    s = _dot("qkgd,skd->kgqs", q, k, dtype, precision) * HD ** -0.5
    causal = jnp.tril(jnp.ones((PREFILL, PREFILL), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
    o = _dot("kgqs,skd->qkgd", p, v, dtype, precision).reshape(PREFILL, D)
    x = x.astype(jnp.float32) + _dot("td,de->te", o, w["wo"], dtype, precision)
    return x + _mlp(_rms(x), w, dtype, precision)


def _small(*args, precision=None):
    acc = jnp.zeros((8, 128), jnp.float32)
    for i, a in enumerate(args):
        acc = acc + jnp.tanh(a.astype(jnp.float32) * (i + 1) * 0.05)
    return acc


@functools.lru_cache(maxsize=None)
def _inputs_fn(name: str):
    def make(key):
        ks = iter(jax.random.split(key, 32))

        def n(shape, scale):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    * scale).astype(jnp.bfloat16)

        w = {"wq": n((D, H * HD), D ** -0.5), "wk": n((D, KH * HD), D ** -0.5),
             "wv": n((D, KH * HD), D ** -0.5), "wo": n((D, D), D ** -0.5),
             "wg": n((D, F), D ** -0.5), "wu": n((D, F), D ** -0.5),
             "wd": n((F, D), F ** -0.5)}
        if name == "decode_layer":
            kv_len = jax.random.randint(next(ks), (ROWS,), 1, CACHE + 1)
            return (n((ROWS, D), 1.0), n((ROWS, CACHE, KH, HD), 1.0),
                    n((ROWS, CACHE, KH, HD), 1.0), kv_len, w)
        if name == "prefill_layer":
            return (n((PREFILL, D), 1.0), w)
        return tuple(n((8, 128), 1.0) for _ in range(SMALL_ARGS))
    return jax.jit(make)


def inputs(name: str, key) -> tuple:
    return _inputs_fn(name)(key)


PROGRAMS = {"decode_layer": _decode, "prefill_layer": _prefill,
            "small_step": _small}


def program(name: str, dtype=jnp.bfloat16):
    """The program, jitted: bf16 matmuls unless ``dtype`` says otherwise."""
    if name == "small_step":
        return jax.jit(PROGRAMS[name])
    return jax.jit(functools.partial(PROGRAMS[name], dtype=dtype))


def reference(name: str):
    """The same program in float32 at ``highest`` precision, jitted."""
    fn = PROGRAMS[name]
    if name == "small_step":
        return jax.jit(functools.partial(fn, precision=jax.lax.Precision.HIGHEST))
    return jax.jit(functools.partial(fn, dtype=jnp.float32,
                                     precision=jax.lax.Precision.HIGHEST))
