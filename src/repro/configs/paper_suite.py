"""The paper's own 'configuration': the characterization suite targets.

The paper's Table I describes the seven GPUs it characterizes. The analog
here is the table of execution targets the suite runs against — the host CPU
backend and the TPU v5e (datasheet peaks). ``suite()`` bundles what the
paper's tool sweeps: the op registry, opt levels, and memory working sets.
"""
from __future__ import annotations

import dataclasses

from repro.core import chains
from repro.core.optlevels import OPT_LEVELS
from repro.core.perfmodel import CPU_HOST, TPU_V5E, HardwareSpec


@dataclasses.dataclass(frozen=True)
class SuiteConfig:
    targets: tuple[HardwareSpec, ...]
    opt_levels: tuple[str, ...]
    categories: tuple[str, ...]
    working_sets: tuple[int, ...]          # Fig. 6 sweep
    chain_lengths: tuple[int, int] = (64, 512)
    reps: int = 30


def suite() -> SuiteConfig:
    return SuiteConfig(
        targets=(CPU_HOST, TPU_V5E),
        opt_levels=tuple(OPT_LEVELS),
        categories=tuple(chains.CATEGORIES),
        working_sets=tuple(1 << k for k in range(12, 26)),
    )
