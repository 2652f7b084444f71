"""Architecture registry: ``get_config(arch_id)`` / ``get_reduced(arch_id)``.

Each module defines ``config()`` (the exact published configuration) and
``reduced()`` (a small same-family config for CPU smoke tests). A share id
(``SHARE_IDS``) names what one chip holds of a stated deployment of an
architecture, built by a function of that architecture's module.
"""
from __future__ import annotations

import importlib

from repro.config import ModelConfig

# assigned architectures (public-literature configs) + the paper's own CNNs
ARCH_IDS = [
    "qwen3_moe_30b_a3b",
    "dbrx_132b",
    "internlm2_1_8b",
    "granite_3_2b",
    "deepseek_coder_33b",
    "gemma2_2b",
    "internvl2_1b",
    "recurrentgemma_9b",
    "musicgen_medium",
    "mamba2_130m",
    "moonlight_16b_a3b",
]

# share id -> (architecture, function of its module giving one chip's share)
SHARE_IDS = {"moonlight_16b_a3b_ep8": ("moonlight_16b_a3b", "ep8_share")}

CNN_IDS = ["vgg16", "resnet18", "resnet34"]

_ALIAS = {i.replace("_", "-"): i for i in ARCH_IDS + CNN_IDS}


def _module(arch_id: str):
    arch_id = _ALIAS.get(arch_id, arch_id)
    if arch_id not in ARCH_IDS + CNN_IDS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS + CNN_IDS}")
    return importlib.import_module(f"repro.configs.{arch_id}")


def get_config(arch_id: str):
    if arch_id in SHARE_IDS:
        arch, fn = SHARE_IDS[arch_id]
        return getattr(_module(arch), fn)()
    return _module(arch_id).config()


def get_reduced(arch_id: str):
    return _module(SHARE_IDS.get(arch_id, (arch_id,))[0]).reduced()


def all_configs() -> dict:
    return {i: get_config(i) for i in ARCH_IDS}
