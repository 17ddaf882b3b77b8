"""The paper's contribution: low-overhead, portable latency characterization.

The characterization *front door* is :mod:`repro.api` (Session / Plan /
Probe / ResultSet) — build a Plan, run it through a Session, get cached,
resumable, failure-tracked sweeps. This package holds the measurement
machinery the probes wrap:

  - chains.default_registry(): the instruction table (8 categories)
  - measure.measure_op/_full(): one op's slope-method latency (+ dispersion)
  - membench.measure_latency(): memory-hierarchy chase (Fig. 6 analog)
  - optlevels: the -O0/-O1/-O3 compiler axis
  - latency_db.LatencyDB: persistent result tables + failures (Table II/III)
  - perfmodel.HloLatencyEstimator: the model-feeding use case
  - hlo_analysis: collective traffic + op histograms from HLO text

Deprecated shims (kept for one release): measure.run_suite,
measure.clock_overhead, membench.sweep — all now route through repro.api.
"""
from repro.core import chains, hlo_analysis, latency_db, measure, membench, optlevels, perfmodel
from repro.core.chains import OpSpec, default_registry
from repro.core.latency_db import LatencyDB, LatencyRecord
from repro.core.perfmodel import CPU_HOST, TPU_V5E, HardwareSpec, HloLatencyEstimator
from repro.core.timing import Measurement, Timer

__all__ = [
    "chains", "hlo_analysis", "latency_db", "measure", "membench", "optlevels",
    "perfmodel", "OpSpec", "default_registry", "LatencyDB", "LatencyRecord",
    "Measurement", "Timer", "HloLatencyEstimator", "HardwareSpec",
    "TPU_V5E", "CPU_HOST",
]
