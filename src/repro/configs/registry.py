"""Architecture registry: --arch <id> resolves here.

Every architecture registers its exact ``ModelConfig`` and a reduced
``smoke`` config of the same family.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Callable

from repro.models.config import ModelConfig

ARCH_IDS = (
    "llama4-maverick-400b-a17b",
    "llama4-scout-17b-a16e",
    "internlm2-20b",
    "granite-3-8b",
    "llama3-405b",
    "yi-9b",
    "jamba-v0.1-52b",
    "xlstm-350m",
    "qwen2-vl-2b",
    "seamless-m4t-large-v2",
)


@dataclasses.dataclass(frozen=True)
class ArchSpec:
    config: ModelConfig
    smoke: ModelConfig


_REGISTRY: dict[str, Callable[[], ArchSpec]] = {}


def register(arch_id: str):
    def deco(fn: Callable[[], ArchSpec]):
        _REGISTRY[arch_id] = fn
        return fn
    return deco


def get(arch_id: str) -> ArchSpec:
    if arch_id not in _REGISTRY:
        mod = arch_id.replace("-", "_").replace(".", "_")
        importlib.import_module(f"repro.configs.{mod}")
    return _REGISTRY[arch_id]()


def all_arch_ids() -> tuple[str, ...]:
    return ARCH_IDS

