"""Assigned architecture configs (exact numbers from the assignment) and
reduced SMOKE variants for CPU tests.

Every module exports CONFIG (full, dry-run only) and SMOKE (tiny,
runnable).  ``get_config(name, smoke=False)`` resolves by arch id.
"""

from __future__ import annotations

import importlib

ARCHS = [
    "grok_1_314b", "granite_moe_3b_a800m", "phi3_medium_14b",
    "phi3_mini_3_8b", "starcoder2_3b", "olmo_1b", "hubert_xlarge",
    "mamba2_370m", "jamba_v0_1_52b", "qwen2_vl_2b",
    "nemotron3_nano_30b_a3b",
]

ALIASES = {a.replace("_", "-"): a for a in ARCHS}


def canonical(name: str) -> str:
    key = name.replace("-", "_").replace(".", "_")
    if key in ARCHS:
        return key
    raise KeyError(f"unknown arch {name!r}; known: {ARCHS}")


def get_config(name: str, smoke: bool = False):
    mod = importlib.import_module(f".{canonical(name)}", __package__)
    return mod.SMOKE if smoke else mod.CONFIG
