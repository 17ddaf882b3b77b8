"""Plain reference of Jamba (AI21 Jamba-v0.1, Jamba 1.5/2 Mini).

Written from the published architecture (arXiv:2403.19887 and the
``jamba`` model type's ``config.json`` keys). Pre-norm blocks in periods of
``attn_layer_period`` layers: layer ``attn_layer_offset`` of each period is
causal grouped-query attention with no positional encoding, the others are
Mamba-1 mixers; layers ``expert_layer_offset`` modulo
``expert_layer_period`` take a mixture of experts, the others a SwiGLU MLP.
The Mamba mixer: input projection to ``x`` and a gate ``z``, a causal
depthwise convolution with bias and SiLU on ``x``, ``x_proj`` to dt, B and
C, each RMS-normed, ``softplus(dt_proj(dt))``, the selective scan token by
token (a sequential ``lax.scan``), the ``D`` skip, the SiLU gate and the
output projection. The router is a linear map to ``router_experts``
outputs, softmax, the top ``num_experts_per_tok``, whose weights are not
renormalized. Final RMSNorm and an untied head. It imports nothing of the
system under test.

Departures from the published model, each also the program's:

* Only the experts ``experts_held`` (``[lo, hi)``) of each MoE layer are
  held and computed, for the tokens routed to them; what the others would
  add is left out. The router keeps all its outputs.
* The vocabulary is the configuration's slice (``vocab_size``); the logits
  are over the slice.
* Weights are random from the seed, in the configuration's type.

Two entry points, as ``dense_decoder.py``: :func:`init_weights` and
:func:`served_gaps`. The forward runs in float32 at ``highest`` matmul
precision, layer by layer and, in a MoE layer, expert by expert, so that its
float32 copies of the weights fit beside the ones in the configuration's
type. Weight leaves are stacked over periods: ``l<j>.<name>`` holds layer
``j`` of every period.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.dense_decoder import HIGHEST, QBLOCK, _gaps, _mm, seed_key


def layout(cfg: dict) -> list[tuple[str, str]]:
    """``(mixer, ffn)`` of each layer of a period, from the offsets."""
    period = cfg["attn_layer_period"]
    return [("attn" if j == cfg["attn_layer_offset"] else "mamba",
             "moe" if j % cfg["expert_layer_period"]
             == cfg["expert_layer_offset"] else "dense")
            for j in range(period)]


def shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Every weight of the configuration and its shape (periods stacked)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, v = d // h, cfg["vocab_size"]
    di, n = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    dtr, k = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    lo, hi = cfg["experts_held"]
    per = cfg["attn_layer_period"]
    p = cfg["num_hidden_layers"] // per
    out = {"embed": (v, d), "lm_head": (v, d), "final_norm": (d,)}
    for j, (mixer, ffn) in enumerate(layout(cfg)):
        if mixer == "attn":
            mix = {"attn_norm": (d,), "wq": (d, h, hd), "wk": (d, kh, hd),
                   "wv": (d, kh, hd), "wo": (h, hd, d)}
        else:
            mix = {"mamba_norm": (d,), "in_proj": (d, 2 * di),
                   "conv_w": (di, k), "conv_b": (di,),
                   "x_proj": (di, dtr + 2 * n), "dt_norm": (dtr,),
                   "b_norm": (n,), "c_norm": (n,), "dt_w": (dtr, di),
                   "dt_b": (di,), "a_log": (di, n), "d_skip": (di,),
                   "out_proj": (di, d)}
        if ffn == "moe":
            e = hi - lo
            mix.update({"ffn_norm": (d,), "router": (d, cfg["router_experts"]),
                        "wg": (e, d, f), "wu": (e, d, f), "wd": (e, f, d)})
        else:
            mix.update({"ffn_norm": (d,), "wg": (d, f), "wu": (d, f),
                        "wd": (f, d)})
        out.update({f"l{j}.{name}": (p,) + s for name, s in mix.items()})
    return out


def _leaf(key, name: str, shape: tuple, dtype: str):
    """One weight from its key: normal at ``fan_in ** -0.5`` for matrices,
    around 1 for norm weights, and Mamba's published forms for ``a_log``
    (S4D-real), ``d_skip`` (ones) and ``dt_b`` (softplus of it log-uniform
    in [0.001, 0.1]). A matrix larger than 256 MiB in float32 is made one
    ``[-2:]`` slice at a time, so that no float32 copy of it exists."""
    base = name.split(".")[-1]
    if base.endswith("norm"):
        return (1.0 + 0.1 * jax.random.normal(key, shape)).astype(dtype)
    if base == "a_log":
        n = shape[-1]
        return jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                                shape).astype(dtype)
    if base == "d_skip":
        return jnp.ones(shape, dtype)
    if base == "dt_b":
        lo, hi = np.log(1e-3), np.log(1e-1)
        dt = jnp.exp(jax.random.uniform(key, shape) * (hi - lo) + lo)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if base == "conv_b":
        return (0.1 * jax.random.normal(key, shape)).astype(dtype)
    if base in ("embed", "lm_head", "conv_w"):
        fan_in = shape[-1]
    elif base in ("wq", "wk", "wv"):
        fan_in = shape[-3]
    elif base == "wo":
        fan_in = shape[-3] * shape[-2]
    else:
        fan_in = shape[-2]

    def normal(k, s):
        return (jax.random.normal(k, s) * fan_in ** -0.5).astype(dtype)

    if 4 * int(np.prod(shape)) <= 1 << 28:
        return normal(key, shape)
    lead = int(np.prod(shape[:-2]))
    return jax.lax.map(lambda i: normal(jax.random.fold_in(key, i), shape[-2:]),
                       jnp.arange(lead)).reshape(shape)


@functools.lru_cache(maxsize=None)
def _init_fn(shape_items: tuple, dtype: str):
    def init(key):
        return {name: _leaf(jax.random.fold_in(key, i), name, shape, dtype)
                for i, (name, shape) in enumerate(shape_items)}
    return jax.jit(init)


def init_weights(seed: int, cfg: dict) -> dict[str, jax.Array]:
    items = tuple(sorted(shapes(cfg).items()))
    return _init_fn(items, cfg["torch_dtype"])(seed_key(seed))


# ---------------------------------------------------------------- layers
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _f32(lw: dict, p) -> dict:
    return {k: jnp.asarray(v[p], jnp.float32) for k, v in lw.items()}


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _attn(x, lw, p, *, eps, quant):
    """Causal grouped-query attention, no positional encoding."""
    lw = _f32(lw, p)
    t = x.shape[0]
    h_, kh, hd = lw["wq"].shape[1], lw["wk"].shape[1], lw["wq"].shape[2]
    h = _rms(x, lw["attn_norm"], eps)
    q = _mm("td,dhk->thk", h, lw["wq"], quant, -1, 0)
    k = jnp.repeat(_mm("td,dhk->thk", h, lw["wk"], quant, -1, 0), h_ // kh, 1)
    v = jnp.repeat(_mm("td,dhk->thk", h, lw["wv"], quant, -1, 0), h_ // kh, 1)
    qblock = min(QBLOCK, t)

    def block(args):
        qb, start = args
        s = _mm("qhd,khd->hqk", qb, k, quant, -1, -1) * hd ** -0.5
        rows = start + jnp.arange(qblock)[:, None]
        s = jnp.where(rows >= jnp.arange(t)[None, :], s, -jnp.inf)
        return _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, quant, -1, 0)

    nb = t // qblock
    o = jax.lax.map(block, (q.reshape(nb, qblock, h_, hd),
                            jnp.arange(nb) * qblock)).reshape(t, h_, hd)
    return x + _mm("thk,hkd->td", o, lw["wo"], quant, (-2, -1), (0, 1))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _mamba(x, lw, p, *, eps, quant):
    """Mamba-1 mixer with dt/B/C norms; the scan runs token by token."""
    lw = _f32(lw, p)
    t = x.shape[0]
    di, k = lw["conv_w"].shape
    dtr, n = lw["dt_w"].shape[0], lw["a_log"].shape[1]
    h = _rms(x, lw["mamba_norm"], eps)
    xz = _mm("td,de->te", h, lw["in_proj"], quant, -1, 0)
    xs, z = xz[:, :di], xz[:, di:]
    pad = jnp.concatenate([jnp.zeros((k - 1, di), jnp.float32), xs])
    conv = sum(pad[j:j + t] * lw["conv_w"][:, j] for j in range(k))
    xs = jax.nn.silu(conv + lw["conv_b"])
    dbc = _mm("ti,ie->te", xs, lw["x_proj"], quant, -1, 0)
    dt = _rms(dbc[:, :dtr], lw["dt_norm"], eps)
    b = _rms(dbc[:, dtr:dtr + n], lw["b_norm"], eps)
    c = _rms(dbc[:, dtr + n:], lw["c_norm"], eps)
    dt = jax.nn.softplus(_mm("tr,ri->ti", dt, lw["dt_w"], quant, -1, 0)
                         + lw["dt_b"])
    a = -jnp.exp(lw["a_log"])                                   # [Di,N]

    def step(state, inp):
        dt_t, x_t, b_t, c_t = inp
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return state, jnp.dot(state, c_t, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32), (dt, xs, b, c))
    y = (y + xs * lw["d_skip"]) * jax.nn.silu(z)
    return x + _mm("ti,id->td", y, lw["out_proj"], quant, -1, 0)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _mlp(x, lw, p, *, eps, quant):
    lw = _f32(lw, p)
    h = _rms(x, lw["ffn_norm"], eps)
    g = _mm("td,df->tf", h, lw["wg"], quant, -1, 0)
    u = _mm("td,df->tf", h, lw["wu"], quant, -1, 0)
    return x + _mm("tf,fd->td", jax.nn.silu(g) * u, lw["wd"], quant, -1, 0)


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "quant"))
def _route(x, norm, router, p, *, eps, top_k, quant):
    """Normed input and each token's weight for each of the router's
    experts: the softmax at its top ``top_k``, zero elsewhere."""
    h = _rms(x, jnp.asarray(norm[p], jnp.float32), eps)
    logits = _mm("td,de->te", h, jnp.asarray(router[p], jnp.float32), quant,
                 -1, 0)
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(gates, top_k)
    comb = jnp.zeros_like(gates).at[jnp.arange(x.shape[0])[:, None],
                                    top_e].set(top_w)
    return h, comb


@functools.partial(jax.jit, static_argnames=("quant",))
def _expert(y, h, comb, wg, wu, wd, p, e, *, quant):
    """``y`` plus one expert's part: its output for every token, times each
    token's weight for it (zero where the router did not pick it). Tokens
    go in blocks of ``2 * QBLOCK``, which bounds the ``[tokens, width]``
    float32 temporaries."""
    wg, wu, wd = (jnp.asarray(a[p, e], jnp.float32) for a in (wg, wu, wd))

    def block(hb):
        g = _mm("td,df->tf", hb, wg, quant, -1, 0)
        u = _mm("td,df->tf", hb, wu, quant, -1, 0)
        return _mm("tf,fd->td", jax.nn.silu(g) * u, wd, quant, -1, 0)

    t, d = h.shape
    o = jax.lax.map(block, h.reshape(-1, min(t, 2 * QBLOCK), d)).reshape(t, d)
    return y + comb[:, None] * o


def _moe(x, lw, p, cfg, quant):
    lo, hi = cfg["experts_held"]
    eps = float(cfg["rms_norm_eps"])
    h, comb = _route(x, lw["ffn_norm"], lw["router"], p, eps=eps,
                     top_k=cfg["num_experts_per_tok"], quant=quant)
    for e in range(hi - lo):
        x = _expert(x, h, comb[:, lo + e], lw["wg"], lw["wu"], lw["wd"], p, e,
                    quant=quant)
    return x


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, final_norm, lm_head, rows, *, eps, quant):
    h = _rms(x[rows], jnp.asarray(final_norm, jnp.float32), eps)
    return _mm("td,vd->tv", h, jnp.asarray(lm_head, jnp.float32), quant,
               -1, -1)


def logits(w, cfg, tokens, rows, quant=None):
    x = jnp.asarray(w["embed"][tokens], jnp.float32)
    eps = float(cfg["rms_norm_eps"])
    per = layout(cfg)
    for i in range(cfg["num_hidden_layers"]):
        p, j = divmod(i, len(per))
        mixer, ffn = per[j]
        lw = {name.split(".", 1)[1]: a for name, a in w.items()
              if name.startswith(f"l{j}.")}
        mix = {k: v for k, v in lw.items()
               if k not in ("ffn_norm", "router", "wg", "wu", "wd")}
        x = (_attn if mixer == "attn" else _mamba)(x, mix, p, eps=eps,
                                                   quant=quant)
        ffn_w = {k: lw[k] for k in ("ffn_norm", "wg", "wu", "wd")}
        if ffn == "moe":
            x = _moe(x, {**ffn_w, "router": lw["router"]}, p, cfg, quant)
        else:
            x = _mlp(x, ffn_w, p, eps=eps, quant=quant)
    return _head(x, w["final_norm"], w["lm_head"], rows, eps=eps, quant=quant)


def served_gaps(w: dict, cfg: dict, prompt, served, length: int,
                out_len: int, quants: tuple[str, ...] = ()
                ) -> tuple[np.ndarray, dict[str, np.ndarray]]:
    """Gaps of the served tokens below the float32 reference's best logit,
    in reference standard deviations, and for each precision in ``quants``
    the gaps of that control's first choices (as ``dense_decoder``'s).

    ``prompt + served[:-1]`` is padded to ``length`` (a multiple of
    :data:`QBLOCK`, or less than it); padding sits after the last real
    position, and attention and the scan are both causal, so it reaches no
    logit read here.
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
    ref = logits(w, cfg, tokens, rows)[:n]
    gaps = np.asarray(_gaps(ref, served))
    controls = {}
    for quant in quants:
        ctl = logits(w, cfg, tokens, rows, quant)[:n]
        controls[quant] = np.asarray(_gaps(ref, jnp.argmax(ctl, axis=-1)))
    return gaps, controls
