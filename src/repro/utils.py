"""Small shared utilities used across the framework."""
from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import math
import os
import tempfile
import time
from typing import Any, Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger("repro")
if not logger.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[repro %(levelname)s %(asctime)s] %(message)s", "%H:%M:%S"))
    logger.addHandler(_h)
    logger.setLevel(os.environ.get("REPRO_LOGLEVEL", "INFO"))


def block(tree: Any) -> Any:
    """Block until every array in a pytree is ready; returns the tree."""
    return jax.block_until_ready(tree)


def compiled_cost(compiled: Any) -> dict[str, Any]:
    """``compiled.cost_analysis()`` normalized to a dict.

    jax < 0.5 returns a one-element list of dicts (one per computation);
    newer jax returns the dict directly.
    """
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost)


def tree_bytes(tree: Any) -> int:
    """Total bytes of all arrays/ShapeDtypeStructs in a pytree."""
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize for l in leaves)


def tree_params(tree: Any) -> int:
    """Total element count of all leaves in a pytree."""
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(tree))


class _JsonEncoder(json.JSONEncoder):
    def default(self, o: Any) -> Any:  # noqa: D102
        if dataclasses.is_dataclass(o) and not isinstance(o, type):
            return dataclasses.asdict(o)
        if isinstance(o, (np.integer,)):
            return int(o)
        if isinstance(o, (np.floating,)):
            return float(o)
        if isinstance(o, np.ndarray):
            return o.tolist()
        return super().default(o)


def dump_json(obj: Any, path: str) -> None:
    """Atomically serialize ``obj`` to ``path``.

    The temp file is uniquely named (two concurrent writers never share one)
    and renamed over the target only after a successful write + fsync, so a
    crash mid-write leaves the previous file intact and no truncated JSON is
    ever observable at ``path``.
    """
    path = os.path.abspath(path)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                               prefix=os.path.basename(path) + ".", suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(obj, f, indent=2, cls=_JsonEncoder)
            f.flush()
            os.fsync(f.fileno())
        # mkstemp creates 0600; restore the umask-derived mode a plain
        # open() would have produced so saved DBs stay readable by others
        umask = os.umask(0)
        os.umask(umask)
        os.chmod(tmp, 0o666 & ~umask)
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def timestamp() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%S")


def parse_kv_notes(notes: str) -> dict[str, str]:
    """Parse the space-separated ``key=value`` convention of record notes.

    Probes persist structured metadata in ``LatencyRecord.notes`` as
    ``ws=8192 line=64 space=vmem``; this is the single inverse for every
    consumer (membench chase points, serving predicted-vs-measured rows).
    Free-text fragments without ``=`` are ignored.
    """
    out: dict[str, str] = {}
    for tok in notes.split():
        if "=" in tok:
            k, _, v = tok.partition("=")
            if k:
                out[k] = v
    return out


def percentiles(samples: Iterable[float],
                ps: Iterable[float] = (50, 90, 99)) -> dict[float, float]:
    """Exact-rank (nearest-rank) percentiles of ``samples``.

    ``percentiles(xs, (50, 90, 99))[99]`` is the smallest element with at
    least 99% of the samples at or below it: ``sorted(xs)[ceil(p/100 * n) - 1]``
    (``p == 0`` gives the minimum). Every returned value is an actual sample —
    no interpolation — so a p99 over latencies is a latency some request
    really saw, and small-sample tails aren't invented by midpoint averaging
    (the ad-hoc ``np.quantile`` default's behavior). This is the single
    percentile implementation for SLO reporting (``traffic.metrics``,
    ``benchmarks/report.py``).
    """
    xs = sorted(float(s) for s in samples)
    if not xs:
        raise ValueError("percentiles() of empty sample set")
    out: dict[float, float] = {}
    for p in ps:
        p = float(p)
        if not 0.0 <= p <= 100.0:
            raise ValueError(f"percentile must be in [0, 100], got {p}")
        rank = math.ceil(p / 100.0 * len(xs))
        out[p] = xs[max(rank, 1) - 1]
    return out


def markdown_table(headers: Iterable[str], rows: Iterable[Iterable[Any]]) -> str:
    headers = list(headers)
    lines = ["| " + " | ".join(str(h) for h in headers) + " |",
             "|" + "|".join("---" for _ in headers) + "|"]
    for row in rows:
        lines.append("| " + " | ".join(str(c) for c in row) + " |")
    return "\n".join(lines)


def flatten_dict(d: Mapping[str, Any], prefix: str = "") -> dict[str, Any]:
    out: dict[str, Any] = {}
    for k, v in d.items():
        key = f"{prefix}.{k}" if prefix else str(k)
        if isinstance(v, Mapping):
            out.update(flatten_dict(v, key))
        else:
            out[key] = v
    return out
