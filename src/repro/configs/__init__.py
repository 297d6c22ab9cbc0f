"""Assigned architecture configs (--arch <id>).

Each module exports CONFIG (full-size, dry-run only) and SMOKE (reduced,
CPU-runnable).  ``get(name)`` resolves by id with '-' or '_' separators.
"""
from __future__ import annotations

import importlib

ARCHS = [
    "h2o_danube_1_8b", "smollm_360m", "granite_3_2b", "stablelm_3b",
    "xlstm_125m", "llava_next_mistral_7b", "jamba_1_5_large_398b",
    "whisper_small", "qwen3_moe_235b_a22b", "deepseek_v3_671b",
    "moonlight_16b_a3b",
]


def canon(name: str) -> str:
    return name.replace("-", "_").replace(".", "_")


def get(name: str, smoke: bool = False):
    cname = canon(name)
    # hillclimb variants ("<arch>+<change>" display names or module keys)
    from . import variants as _v
    vkey = cname.replace("+", "_")
    if vkey in _v.VARIANTS:
        return _v.VARIANTS[vkey]
    mod = importlib.import_module(f"repro.configs.{cname}")
    return mod.SMOKE if smoke else mod.CONFIG


def all_archs():
    return list(ARCHS)
