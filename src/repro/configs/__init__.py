from repro.configs import registry
from repro.configs.registry import ARCH_IDS, ArchSpec, all_arch_ids, get

__all__ = ["registry", "ARCH_IDS", "ArchSpec", "all_arch_ids", "get"]
