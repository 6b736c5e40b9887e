"""Architecture config registry — ``--arch <id>`` resolution.

Each module defines the exact published CONFIG plus a ``reduced()`` smoke
variant of the same family (same block pattern, tiny dims).
"""
from __future__ import annotations

from typing import Dict, List

from repro.models.transformer import ArchConfig

from repro.configs import (  # noqa: E402
    internvl2_26b,
    jamba_1_5_large_398b,
    minitron_4b,
    mixtral_8x22b,
    moonlight_16b_a3b,
    qwen1_5_4b,
    qwen2_1_5b,
    smollm_360m,
    whisper_base,
    xlstm_1_3b,
)

_MODULES = {
    "moonlight-16b-a3b": moonlight_16b_a3b,
    "mixtral-8x22b": mixtral_8x22b,
    "xlstm-1.3b": xlstm_1_3b,
    "jamba-1.5-large-398b": jamba_1_5_large_398b,
    "qwen1.5-4b": qwen1_5_4b,
    "minitron-4b": minitron_4b,
    "smollm-360m": smollm_360m,
    "qwen2-1.5b": qwen2_1_5b,
    "internvl2-26b": internvl2_26b,
    "whisper-base": whisper_base,
}

ARCH_NAMES: List[str] = list(_MODULES)


def get_config(name: str, reduced: bool = False) -> ArchConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; one of {ARCH_NAMES}")
    mod = _MODULES[name]
    return mod.reduced() if reduced else mod.CONFIG


def all_configs(reduced: bool = False) -> Dict[str, ArchConfig]:
    return {n: get_config(n, reduced) for n in ARCH_NAMES}
