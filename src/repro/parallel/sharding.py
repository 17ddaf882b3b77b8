"""Logical-axis sharding: DP / FSDP / TP / EP / CP / SP on a device mesh.

Models annotate parameters (via :class:`Param` boxes) and activations (via
:func:`annotate`) with *logical* axis names; a :class:`ShardingRules` table
resolves those to mesh axes. Resolution enforces even divisibility (GSPMD
rejects uneven input shardings — verified empirically) and falls back to
replication otherwise, so e.g. 40 query heads on a 16-way model axis
automatically degrade to the context-parallel attention path (DESIGN.md §6).
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Callable, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Param boxes
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Param:
    """A parameter leaf tagged with logical axis names (one per dim)."""

    value: Any
    axes: tuple[str | None, ...]

    def tree_flatten(self):
        return (self.value,), self.axes

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], tuple(aux))


def is_param(x: Any) -> bool:
    return isinstance(x, Param)


def unbox(tree: Any) -> Any:
    """Strip Param boxes -> raw array pytree."""
    return jax.tree_util.tree_map(lambda p: p.value, tree, is_leaf=is_param)


def boxed_axes(tree: Any) -> Any:
    """Matching pytree of logical-axes tuples."""
    return jax.tree_util.tree_map(lambda p: p.axes, tree, is_leaf=is_param)


def _is_axes_leaf(x: Any) -> bool:
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def rebox(values: Any, axes: Any) -> Any:
    leaves_v = jax.tree_util.tree_leaves(values)
    leaves_a, tda = jax.tree_util.tree_flatten(axes, is_leaf=_is_axes_leaf)
    assert len(leaves_v) == len(leaves_a), (len(leaves_v), len(leaves_a))
    return jax.tree_util.tree_unflatten(
        tda, [Param(v, a) for v, a in zip(leaves_v, leaves_a)])


def with_layer_axis(tree: Any, name: str = "layers") -> Any:
    """After vmap-stacked init, prefix every Param's axes with ``name``."""
    return jax.tree_util.tree_map(
        lambda p: Param(p.value, (name,) + p.axes), tree, is_leaf=is_param)


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------
MeshAxes = tuple[str, ...] | str | None


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis -> mesh axes. Missing names replicate."""

    mapping: Mapping[str, MeshAxes]

    def resolve(self, axes: Sequence[str | None], shape: Sequence[int] | None,
                mesh: Mesh) -> P:
        used: set[str] = set()
        out: list[MeshAxes] = []
        for i, name in enumerate(axes):
            m = self.mapping.get(name) if name else None
            if m is None:
                out.append(None)
                continue
            parts = (m,) if isinstance(m, str) else tuple(m)
            parts = tuple(p for p in parts if p in mesh.shape and p not in used)
            if not parts:
                out.append(None)
                continue
            size = int(np.prod([mesh.shape[p] for p in parts]))
            if shape is not None and shape[i] % size != 0:
                # uneven -> replicate (GSPMD requires divisibility); callers
                # that care (attention) pick CP instead via policy.
                out.append(None)
                continue
            used.update(parts)
            out.append(parts if len(parts) > 1 else parts[0])
        return P(*out)


# Megatron-style LM defaults; per-arch configs override (see configs/).
def lm_rules(*, fsdp: bool = True, context_parallel_seq: bool = False,
             fsdp_axes: MeshAxes = ("pod", "data")) -> ShardingRules:
    m: dict[str, MeshAxes] = {
        # activations
        "batch": ("pod", "data"),
        "seq": None,
        "act_embed": None,
        "act_heads": "model",
        "act_mlp": "model",
        "act_vocab": "model",
        "cp_seq": "model" if context_parallel_seq else None,
        "kv_seq": "model",    # decode caches: flash-decode partial softmax
        "kv_hd": "model",     # decode caches: split-K alternative
        # params
        "embed": fsdp_axes if fsdp else None,
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "qkv_dim": "model",
        "mlp": "model",
        "vocab": "model",
        "experts": "model",
        "expert_mlp": None,
        "ssm_inner": "model",
        "ssm_state": None,
        "lstm_inner": "model",
        "layers": None,
        "conv": None,
    }
    return ShardingRules(m)


# ---------------------------------------------------------------------------
# Context: active (mesh, rules); annotate() is a no-op outside it, so smoke
# tests run the same model code without any mesh.
# ---------------------------------------------------------------------------
_CTX: contextvars.ContextVar[tuple[Mesh, ShardingRules] | None] = \
    contextvars.ContextVar("sharding_ctx", default=None)


def _mesh_ctx(mesh: Mesh):
    """Enter the mesh with whatever this jax version provides.

    jax >= 0.5 has jax.set_mesh; some 0.4.x ship jax.sharding.use_mesh; on
    older jax the explicit NamedSharding(mesh, ...) paths below don't need a
    global mesh at all, so fall back to a no-op.
    """
    if hasattr(jax, "set_mesh"):
        return jax.set_mesh(mesh)
    if hasattr(jax.sharding, "use_mesh"):
        return jax.sharding.use_mesh(mesh)
    return contextlib.nullcontext()


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: ShardingRules):
    tok = _CTX.set((mesh, rules))
    try:
        with _mesh_ctx(mesh):
            yield
    finally:
        _CTX.reset(tok)


def current() -> tuple[Mesh, ShardingRules] | None:
    return _CTX.get()


def annotate(x: jax.Array, *axes: str | None) -> jax.Array:
    """Constrain an activation's sharding by logical axes (no-op w/o mesh)."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = rules.resolve(axes, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def param_shardings(boxed: Any, mesh: Mesh, rules: ShardingRules) -> Any:
    """NamedSharding pytree for a boxed param tree (for jit in_shardings)."""
    def one(p: Param):
        shape = getattr(p.value, "shape", None)
        return NamedSharding(mesh, rules.resolve(p.axes, shape, mesh))
    return jax.tree_util.tree_map(one, boxed, is_leaf=is_param)


def opt_shardings(params_boxed: Any, state_shapes: Any, mesh: Mesh,
                  rules: ShardingRules) -> dict:
    """Optimizer-state shardings mirror the owning param's sharding."""
    def for_param(p: Param, st):
        spec = rules.resolve(p.axes, p.value.shape, mesh)
        if isinstance(st, dict) and set(st) == {"q", "scale"}:
            # int8 moments: q has the param's shape (inherits its sharding);
            # scale is [..., nblocks] — keep the last-axis sharding only if
            # the block count still divides.
            entries = list(spec) + [None] * (len(p.value.shape) - len(list(spec)))
            s_entries = list(entries)
            last = s_entries[-1] if s_entries else None
            nb = st["scale"].shape[-1]
            if last is not None:
                size = 1
                for a in ((last,) if isinstance(last, str) else last):
                    size *= mesh.shape.get(a, 1)
                if nb % size != 0:
                    s_entries[-1] = None
            return {"q": NamedSharding(mesh, P(*entries)),
                    "scale": NamedSharding(mesh, P(*s_entries))}
        return NamedSharding(mesh, spec)

    m_sh = jax.tree_util.tree_map(for_param, params_boxed, state_shapes["m"],
                                  is_leaf=is_param)
    v_sh = jax.tree_util.tree_map(for_param, params_boxed, state_shapes["v"],
                                  is_leaf=is_param)
    return {"m": m_sh, "v": v_sh,
            "count": NamedSharding(mesh, P())}


def cache_shardings(cache_shapes: Any, mesh: Mesh, batch_axes,
                    mode: str = "seq") -> Any:
    """KV-cache shardings by leaf name: batch over data axes, SSM inner dims
    over 'model', and the KV cache either ``seq``-sharded over 'model'
    (flash-decode partial softmax) or ``head_dim``-sharded (split-K attention:
    the decode step's in-place cache write stays shard-local); ``mode`` is
    ``Runtime.cache_shard``."""
    def one(path, sds):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        nd = len(sds.shape)
        def div(i, ax):
            if ax is None:
                return False
            size = 1
            for a in ((ax,) if isinstance(ax, str) else ax):
                size *= mesh.shape.get(a, 1)
            return sds.shape[i] % size == 0 and size > 1
        spec: list = [None] * nd
        if nd >= 2 and div(1, batch_axes):
            spec[1] = batch_axes        # [layers, batch, ...]
        if name in ("k", "v", "ck", "cv") and nd == 5:
            if mode == "head_dim" and div(4, "model"):
                spec[4] = "model"       # split-K: local DUS, psum'd logits
            elif div(2, "model"):
                spec[2] = "model"       # cache seq (flash-decode combine)
        elif name == "h" and nd == 4 and div(2, "model"):
            spec[2] = "model"           # mamba inner
        elif name == "conv" and nd == 4 and div(3, "model"):
            spec[3] = "model"
        elif name == "c" and nd == 5 and div(4, "model"):
            spec[4] = "model"           # mlstm value dim
        elif name in ("n",) and nd == 4 and div(3, "model"):
            spec[3] = "model"
        elif name in ("c", "n", "h", "m") and nd == 3 and div(2, "model"):
            spec[2] = "model"           # slstm [layers,B,D]
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, cache_shapes)


def spec_tree(boxed: Any, mesh: Mesh, rules: ShardingRules) -> Any:
    def one(p: Param):
        shape = getattr(p.value, "shape", None)
        return rules.resolve(p.axes, shape, mesh)
    return jax.tree_util.tree_map(one, boxed, is_leaf=is_param)


def shard_like(tree: Any, shardings: Any) -> Any:
    """with_sharding_constraint a raw pytree with a sharding pytree."""
    return jax.tree_util.tree_map(jax.lax.with_sharding_constraint, tree, shardings)
