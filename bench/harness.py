"""What every cell shares: finding its files by name, the device, the
compile cache, the profiler window and the result line.

A cell is ``bench/cells/<name>.json``. It names a configuration
(``bench/configs/<config>.json``), a traffic mix (``bench/traffic/<mix>.json``)
and a driver (``bench/drivers/<driver>.py``). A configuration names its
family, whose plain reference is ``bench/models/<family>.py`` and whose
mapping onto the program is ``bench/adapters/<family>.py``. A metric
``<metric>`` of ``BENCHMARK.json`` is read by ``bench/metrics/<metric>.py``.
Adding any of them is adding files; nothing here lists them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """Raised by a driver's hook at the first call after the window closed."""


def now() -> float:
    return time.perf_counter()


# ------------------------------------------------------------------ files
def load_json(root: str, *parts: str) -> Any:
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> dict:
    cell = load_json(root, "bench", "cells", f"{name}.json")
    cell["config_data"] = load_json(root, "bench", "configs",
                                    f"{cell['config']}.json")
    cell["traffic_data"] = load_json(root, "bench", "traffic",
                                     f"{cell['traffic']}.json")
    return cell


def _module(root: str, *parts: str):
    path = os.path.join(root, *parts)
    name = "bench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def driver(root: str, cell: dict):
    return _module(root, "bench", "drivers", f"{cell['driver']}.py")


def reference(root: str, cfg: dict):
    return _module(root, "bench", "models", f"{cfg['family']}.py")


def adapter(root: str, cfg: dict):
    return _module(root, "bench", "adapters", f"{cfg['family']}.py")


def metric_reader(root: str, name: str):
    return _module(root, "bench", "metrics", f"{name}.py")


def metrics_for(root: str, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics of BENCHMARK.json that
    apply to ``cell``: those with no ``workloads`` key and those that name
    it."""
    spec = load_json(root, "BENCHMARK.json")
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


def peaks(root: str, device_kind: str) -> dict:
    table = load_json(root, "bench", "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


# ----------------------------------------------------------------- device
def require_chips(n: int):
    """The first ``n`` accelerator devices; never falls back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoAccelerator("JAX found no accelerator (platform cpu)")
    if len(devices) < n:
        raise NoAccelerator(f"the cell needs {n} chips, JAX found "
                            f"{len(devices)}")
    return devices[:n]


def compile_cache_dir(root: str) -> str:
    """``bench/.cache/jax`` in the checkout, whatever the environment says:
    two checkouts never share a cache."""
    return os.path.join(root, "bench", ".cache", "jax")


def use_compile_cache(path: str) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program in it, so that only a checkout's first run compiles."""
    import jax

    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak_bytes(devices) -> int | None:
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in devices]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


# ---------------------------------------------------------------- the run
@dataclasses.dataclass
class Run:
    """One run of one cell: its inputs, and what its driver measured.

    Drivers fill ``window`` (host-clock start and end of the measured
    window), ``setup_s``, ``attempted``, ``failed``, ``checks`` and whatever
    their metric readers read; the harness fills the device fields."""

    root: str
    cell: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list = dataclasses.field(default_factory=list)
    window: tuple[float, float] = (0.0, 0.0)
    setup_s: float = math.nan
    attempted: int = 0
    failed: int = 0
    checks: dict = dataclasses.field(default_factory=dict)
    memory_peak_bytes: int | None = None
    profile: dict | None = None       # the reduced trace, --trace 1 only
    data: dict = dataclasses.field(default_factory=dict)

    @property
    def cfg(self) -> dict:
        return self.cell["config_data"]

    @property
    def mix(self) -> dict:
        return self.cell["traffic_data"]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def peak(self, key: str) -> float:
        return float(peaks(self.root, self.devices[0].device_kind)[key])

    def span(self, name: str):
        """A host span in the profiler's trace (``--trace 1``), else nothing."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def mark(self, name: str) -> None:
        with self.span(name):
            pass

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks[name] = (float(value), float(limit))


class Profile:
    """The profiler over the measured window, in a run of its own
    (``--trace 1``). Drivers start it before the work that leads into the
    window and mark the window's two ends; :meth:`stop` reduces the trace
    (``bench/trace_reduce.py``) and deletes it."""

    def __init__(self, run: Run):
        self.run = run
        self.dir = os.path.join(run.root, "bench", ".cache", "trace")
        self.active = False

    def start(self) -> None:
        if not self.run.trace:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        from bench import trace_reduce

        jax.profiler.stop_trace()
        self.active = False
        try:
            self.run.profile = trace_reduce.reduce_dir(
                self.dir, n_chips=len(self.run.devices))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def metrics(run: Run) -> dict:
    """The cell's metrics for this kind of run, each read by its reader;
    a reader that finds nothing to read returns None and is left out."""
    kind = "per_layer" if run.trace else "end_to_end"
    out = {}
    for m in metrics_for(run.root, run.cell["name"], kind):
        value = metric_reader(run.root, m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result(run: Run) -> dict:
    d0 = run.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": bool(run.checks) and all(
               math.isfinite(v) and v <= lim for v, lim in run.checks.values()),
           "attempted": int(run.attempted), "failed": int(run.failed),
           "metrics": metrics(run), "device": device}
    if run.trace and run.profile is not None:
        device["busy_s"] = run.profile["busy_s"]
        device["window_s"] = run.profile["window_s"]
        out["breakdown"] = {"device_ops": run.profile["device_ops"][:10],
                            "idle_gaps": run.profile["idle_gaps"][:10]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in run.checks.items()}
    return out
