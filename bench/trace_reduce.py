"""From a profiler trace to the numbers the benchmark reads.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``. Device planes are those named ``/device:...``;
on each, the operations are the events of its ``XLA Ops`` line (all its
lines where it has none), named by their HLO instruction (:func:`op_name`).
The benchmark's own host spans are the events whose names start with
``bench.``; ``bench.window_start`` and
``bench.window_end`` mark the measured window on the trace's clock.

* ``busy_s``: the union of the operations' intervals inside the window,
  averaged over the chips;
* ``window_s``: the window's length;
* ``device_ops``: seconds per operation name inside the window, most first;
* ``idle_gaps``: seconds in which no operation ran, by the innermost
  benchmark span open in the middle of each gap (``none`` where no span
  was), most first.
"""
from __future__ import annotations

import bisect
import glob
import os

WINDOW_START, WINDOW_END = "bench.window_start", "bench.window_end"


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_events(device_ops: dict[str, list[tuple[float, float, str]]],
                  host_spans: list[tuple[float, float, str]]) -> dict:
    """``device_ops``: per device plane, ``(start_ns, end_ns, name)`` of its
    operations; ``host_spans``: the same of the benchmark's host spans."""
    starts = [s for s, _, n in host_spans if n == WINDOW_START]
    ends = [s for s, _, n in host_spans if n == WINDOW_END]
    if not starts or not ends:
        raise ValueError("trace holds no bench.window_start/window_end marks")
    w0, w1 = min(starts), max(ends)
    window_ns = w1 - w0
    busy = []
    by_name: dict[str, float] = {}
    gaps_by_span: dict[str, float] = {}
    spans = sorted((s, e, n) for s, e, n in host_spans
                   if n not in (WINDOW_START, WINDOW_END))
    span_starts = [s for s, _, _ in spans]

    def label(t: float) -> str:
        best, best_len = "none", float("inf")
        i = bisect.bisect_right(span_starts, t)
        for s, e, n in spans[max(0, i - 64):i]:
            if s <= t < e and e - s < best_len:
                best, best_len = n, e - s
        return best

    for ops in device_ops.values():
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in ops
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            by_name[n] = by_name.get(n, 0.0) + (e - s) * 1e-9
        merged = _union((s, e) for s, e, _ in clipped)
        busy.append(sum(e - s for s, e in merged))
        t = w0
        for s, e in merged + [[w1, w1]]:
            if s > t:
                lab = label((s + t) / 2)
                gaps_by_span[lab] = gaps_by_span.get(lab, 0.0) + (s - t) * 1e-9
            t = max(t, e)
    n = max(len(device_ops), 1)
    for k in gaps_by_span:
        gaps_by_span[k] /= n
    return {"busy_s": sum(busy) / n * 1e-9, "window_s": window_ns * 1e-9,
            "device_ops": sorted(([k, v] for k, v in by_name.items()),
                                 key=lambda kv: -kv[1]),
            "idle_gaps": sorted(([k, v] for k, v in gaps_by_span.items()),
                                key=lambda kv: -kv[1])}


def op_name(text: str) -> str:
    """An operation's short name: the HLO instruction's name, and for a
    custom call (a Pallas kernel) its result type too."""
    name, _, rest = text.partition(" = ")
    if " custom-call(" in rest:
        name += " " + rest.split(" custom-call(")[0]
    return name


def read_xplane(path: str, n_chips: int | None = None):
    """``(device_ops, host_spans)`` of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: dict[str, list] = {}
    host_spans: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            evs = [(e.start_ns, e.end_ns, op_name(e.name))
                   for ln in ops for e in ln.events if e.duration_ns > 0]
            if evs:
                device_ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host_spans += [(e.start_ns, e.end_ns, e.name)
                               for e in ln.events
                               if e.name.startswith("bench.")]
    if n_chips is not None and len(device_ops) > n_chips:
        keep = sorted(device_ops, key=lambda k: -len(device_ops[k]))[:n_chips]
        device_ops = {k: device_ops[k] for k in keep}
    return device_ops, host_spans


def reduce_dir(log_dir: str, n_chips: int | None = None) -> dict:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    device_ops, host_spans = read_xplane(paths[-1], n_chips)
    if not device_ops:
        raise ValueError("the trace holds no device operations")
    return reduce_events(device_ops, host_spans)
