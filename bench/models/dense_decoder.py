"""Plain reference of a dense grouped-query-attention decoder.

Written from the published architecture (InternLM2, Yi and other
LLaMA-style decoders): pre-norm RMSNorm, rotate-half RoPE, causal
grouped-query attention, SwiGLU MLP, final RMSNorm and an untied output
head. It imports nothing of the system under test.

Two entry points:

* :func:`init_weights` makes the weights of one configuration from a seed,
  on the device, in the type the configuration states, in one jitted call.
  The benchmark hands the same arrays to the program (through its adapter)
  and makes them again for this reference once the program's state is gone.
* :func:`served_gaps` runs the float32 reference, at ``highest`` matmul
  precision and layer by layer, over a prompt and the tokens the program
  served, and returns, per served token, how far its logit lies below the
  reference's best, in units of the reference row's standard deviation.
  With ``quants`` it also reads controls: the same forward with every
  matmul input rounded to a lower precision, whose first choice at each
  position is then judged by the float32 logits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# Query rows per attention block of the reference: bounds its score tile to
# heads x QBLOCK x length float32 values.
QBLOCK = 512


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every weight of the configuration and its shape (layers stacked)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, n, v = d // h, cfg["num_hidden_layers"], cfg["vocab_size"]
    return {"embed": (v, d), "lm_head": (v, d), "final_norm": (d,),
            "attn_norm": (n, d), "wq": (n, d, h, hd), "wk": (n, d, kh, hd),
            "wv": (n, d, kh, hd), "wo": (n, h, hd, d), "mlp_norm": (n, d),
            "wg": (n, d, f), "wu": (n, d, f), "wd": (n, f, d)}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the low and high 32-bit words)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@functools.lru_cache(maxsize=None)
def _init_fn(shape_items: tuple, dtype: str):
    def init(key):
        out = {}
        for i, (name, shape) in enumerate(shape_items):
            k = jax.random.fold_in(key, i)
            if name.endswith("norm"):
                # around 1, so that a norm weight applied wrongly shows
                w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
            else:
                fan_in = shape[1] if name in ("embed", "lm_head") else (
                    shape[1] * shape[2] if name == "wo" else shape[1])
                w = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
            out[name] = w.astype(dtype)
        return out
    return jax.jit(init)


def init_weights(seed: int, cfg: dict) -> dict[str, jax.Array]:
    items = tuple(sorted(shapes(cfg).items()))
    return _init_fn(items, cfg["torch_dtype"])(seed_key(seed))


# ------------------------------------------------------------- precision
def _fake_quant(x, quant: str | None, axis: int):
    """Round ``x`` to ``quant`` with one scale per slice along ``axis``
    (the reduction axis of the matmul it feeds), back in float32."""
    if quant is None:
        return x
    if quant == "int8":
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        return jnp.round(x / scale).clip(-127, 127) * scale
    if quant == "fp8":
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
        scale = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {quant!r}")


def _mm(spec: str, a, w, quant: str | None, a_axis: int, w_axis):
    a = _fake_quant(a, quant, a_axis)
    if quant is not None:
        w = _fake_quant(w, quant, w_axis)
    return jnp.einsum(spec, a, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):                     # x: [T, H, hd]
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None] * freqs          # [T, 1, hd/2]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "quant"))
def _layer(x, w, i, *, eps, theta, quant):
    """One decoder layer over ``x`` [T, D] float32 (causal from position 0)."""
    lw = {k: jnp.asarray(w[k][i], jnp.float32)
          for k in ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                    "wg", "wu", "wd")}
    t = x.shape[0]
    h_, kh, hd = lw["wq"].shape[1], lw["wk"].shape[1], lw["wq"].shape[2]
    pos = jnp.arange(t, dtype=jnp.float32)
    h = _rms(x, lw["attn_norm"], eps)
    q = _rope(_mm("td,dhk->thk", h, lw["wq"], quant, -1, 0), pos, theta)
    k = _rope(_mm("td,dhk->thk", h, lw["wk"], quant, -1, 0), pos, theta)
    v = _mm("td,dhk->thk", h, lw["wv"], quant, -1, 0)
    k = jnp.repeat(k, h_ // kh, axis=1)        # query head j reads kv j // g
    v = jnp.repeat(v, h_ // kh, axis=1)
    nb = t // QBLOCK

    def block(args):
        qb, start = args                       # [QBLOCK, H, hd]
        s = _mm("qhd,khd->hqk", qb, k, quant, -1, -1) * hd ** -0.5
        rows = start + jnp.arange(QBLOCK)[:, None]
        s = jnp.where(rows >= jnp.arange(t)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("hqk,khd->qhd", p, v, quant, -1, 0)

    o = jax.lax.map(block, (q.reshape(nb, QBLOCK, h_, hd),
                            jnp.arange(nb) * QBLOCK)).reshape(t, h_, hd)
    x = x + _mm("thk,hkd->td", o, lw["wo"], quant, (-2, -1), (0, 1))
    h = _rms(x, lw["mlp_norm"], eps)
    g = _mm("td,df->tf", h, lw["wg"], quant, -1, 0)
    u = _mm("td,df->tf", h, lw["wu"], quant, -1, 0)
    return x + _mm("tf,fd->td", jax.nn.silu(g) * u, lw["wd"], quant, -1, 0)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, final_norm, lm_head, rows, *, eps, quant):
    h = _rms(x[rows], jnp.asarray(final_norm, jnp.float32), eps)
    return _mm("td,vd->tv", h, jnp.asarray(lm_head, jnp.float32), quant,
               -1, -1)


def _logits(w, cfg, tokens, rows, quant):
    x = jnp.asarray(w["embed"][tokens], jnp.float32)
    kw = dict(eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
              quant=quant)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, w, i, **kw)
    return _head(x, w["final_norm"], w["lm_head"], rows,
                 eps=kw["eps"], quant=quant)


@jax.jit
def _gaps(ref, tokens):
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, tokens[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.std(ref, axis=-1)


def served_gaps(w: dict, cfg: dict, prompt, served, length: int,
                out_len: int, quants: tuple[str, ...] = ()
                ) -> tuple[np.ndarray, dict[str, np.ndarray]]:
    """Gaps of the served tokens below the float32 reference's best logit,
    in reference standard deviations, and for each precision in ``quants``
    the gaps of that control's first choices.

    ``prompt + served[:-1]`` is padded to ``length`` (a multiple of
    :data:`QBLOCK`) and the logit rows read to ``out_len``, so every request
    runs one compiled shape; padding sits after the last real position and
    causality keeps it out of every logit read here.
    """
    seq = list(prompt) + list(served[:-1])
    n = len(served)
    if len(seq) > length or n > out_len:
        raise ValueError(f"sequence of {len(seq)} (or {n} served) exceeds "
                         f"{length} ({out_len})")
    tokens = np.zeros(length, np.int32)
    tokens[:len(seq)] = seq
    rows = np.zeros(out_len, np.int32)
    rows[:n] = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
    tokens, rows = jnp.asarray(tokens), jnp.asarray(rows)
    served = jnp.asarray(np.asarray(served, np.int32))
    ref = _logits(w, cfg, tokens, rows, None)[:n]
    gaps = np.asarray(_gaps(ref, served))
    controls = {}
    for quant in quants:
        ctl = _logits(w, cfg, tokens, rows, quant)[:n]
        controls[quant] = np.asarray(_gaps(ref, jnp.argmax(ctl, axis=-1)))
    return gaps, controls
