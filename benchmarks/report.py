"""Print the serving SLO table (predicted against measured percentiles)
from the samples ``bench_serving_slo`` saved under ``benchmarks/results/``.

  PYTHONPATH=src:. python benchmarks/report.py > /tmp/report.md
"""
from __future__ import annotations

import glob
import json
import os

from repro.utils import markdown_table, percentiles

RES = os.path.join(os.path.dirname(__file__), "results")


def _load(pattern):
    out = []
    for f in sorted(glob.glob(pattern)):
        try:
            out.append(json.load(open(f)))
        except Exception:  # noqa: BLE001
            pass
    return out


def serving_slo_table() -> str:
    """Predicted-vs-measured SLO percentiles recomputed from the raw
    per-request samples ``bench_serving_slo`` persisted — exact-rank
    (``repro.utils.percentiles``), so the table can report any percentile
    the stored summaries didn't, and every value is an actual request."""
    rows = []
    for rec in _load(f"{RES}/serving_slo.json"):
        for rate in rec.get("rates", []):
            for side in ("predicted", "measured"):
                samples = rate.get(f"{side}_samples", [])
                ttfts = [s["ttft_ns"] for s in samples]
                tpots = [s["tpot_ns"] for s in samples
                         if s["tpot_ns"] is not None]
                if not ttfts:
                    continue
                tt = percentiles(ttfts, (50, 90, 99))
                tp = (percentiles(tpots, (50, 90, 99)) if tpots
                      else {50: 0.0, 90: 0.0, 99: 0.0})
                rows.append([
                    f"{rate['rate_rps']:g}", side, len(samples),
                    f"{tt[50] / 1e6:.3f}", f"{tt[90] / 1e6:.3f}",
                    f"{tt[99] / 1e6:.3f}", f"{tp[50] / 1e6:.3f}",
                    f"{tp[99] / 1e6:.3f}",
                    f"{rate[side]['goodput_tok_s']:.1f}"])
    return markdown_table(
        ["rate (req/s)", "side", "n", "TTFT p50 (ms)", "TTFT p90", "TTFT p99",
         "TPOT p50", "TPOT p99", "goodput (tok/s)"], rows)


if __name__ == "__main__":
    print("## Serving SLO (predicted vs measured)\n")
    print(serving_slo_table())
