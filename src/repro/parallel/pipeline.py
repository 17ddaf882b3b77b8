"""GPipe-style pipeline parallelism via shard_map + ppermute (opt-in PP).

Stages live on the ``stage`` mesh axis (on a multi-pod mesh this is the
``pod`` axis: one pipeline stage per pod, DP x TP inside the pod). The
schedule is the classic GPipe fill-drain loop: T = M + S - 1 ticks, activations
hop stage->stage+1 by collective-permute each tick, microbatch i occupies
stage s at tick i+s. Bubble fraction = (S-1)/(M+S-1), reported by
``bubble_fraction`` so launchers can budget microbatches.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_forward(stage_fn: Callable[[Any, jax.Array], jax.Array],
                     stage_params: Any, x_micro: jax.Array, mesh: Mesh,
                     axis: str = "pod") -> jax.Array:
    """Run ``stage_fn`` as an S-stage pipeline.

    stage_params: pytree with leading dim S (sharded over ``axis``).
    x_micro: [M, mb, ...] microbatched input (replicated across stages).
    Returns [M, mb, ...] outputs (from the last stage, broadcast).
    """
    s = mesh.shape[axis]

    def body(params, xs):                    # params: leading dim 1 (local)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        m = xs.shape[0]
        ticks = m + s - 1
        stage = lax.axis_index(axis)
        perm = [(j, (j + 1) % s) for j in range(s - 1)]   # open chain
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any) — others use the hop input
            feed = jnp.where(t < m, t, m - 1)
            inp = jnp.where(stage == 0,
                            xs[feed].astype(buf.dtype), buf)
            out = stage_fn(params, inp)
            # last stage emits microbatch t-(s-1)
            emit = t - (s - 1)
            do_emit = jnp.logical_and(stage == s - 1, emit >= 0)
            idx = jnp.clip(emit, 0, m - 1)
            outs = lax.cond(
                do_emit, lambda o: o.at[idx].set(out), lambda o: o, outs)
            buf = lax.ppermute(out, axis, perm)
            return buf, outs

        _, outs = lax.fori_loop(0, ticks, tick, (buf, outs))
        # broadcast final outputs from the last stage to everyone (psum of
        # a one-hot-by-stage buffer == broadcast)
        return lax.psum(jnp.where(stage == s - 1, outs, jnp.zeros_like(outs)),
                        axis)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(axis), P(*([None] * x_micro.ndim))),
                   out_specs=P(*([None] * x_micro.ndim)),
                   check_rep=False)
    return fn(stage_params, x_micro)


def reference_forward(stage_fn, stage_params, x_micro: jax.Array) -> jax.Array:
    """Sequential oracle for tests."""
    def run_one(x):
        s = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
        for i in range(s):
            p = jax.tree_util.tree_map(lambda a: a[i], stage_params)
            x = stage_fn(p, x)
        return x
    return jax.vmap(run_one)(x_micro)
