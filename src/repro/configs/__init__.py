"""Architecture registry: ``get_config(name)`` / ``get_smoke(name)``.

LM archs are the 10 assigned architectures; XR archs are the paper's own
workloads; training-only archs (Kimi-VL) run through the LM trainer alone.
``--arch <id>`` anywhere in the launchers resolves through here.
"""
from __future__ import annotations

import importlib
from typing import Dict, List, Union

from repro.configs.base import ModelConfig, XRConfig, smoke, smoke_xr

_MODULES: Dict[str, str] = {
    # --- assigned LM-family architectures ---
    "phi-3-vision-4.2b": "phi3_vision_4p2b",
    "gemma2-9b": "gemma2_9b",
    "deepseek-7b": "deepseek_7b",
    "yi-34b": "yi_34b",
    "llama3.2-1b": "llama3p2_1b",
    "mixtral-8x7b": "mixtral_8x7b",
    "grok-1-314b": "grok1_314b",
    "mamba2-1.3b": "mamba2_1p3b",
    "jamba-1.5-large-398b": "jamba_1p5_large_398b",
    "whisper-small": "whisper_small",
    # --- training-only LM-family configurations ---
    "kimi-vl-a3b": "kimi_vl_a3b",
    # --- paper XR workloads ---
    "detnet": "detnet",
    "edsnet": "edsnet",
}

XR_ARCHS: List[str] = ["detnet", "edsnet"]
# Trained through ``train.loop.run_lm_training``; no decode path, so not
# among the dry-run's train/prefill/decode cells.
TRAIN_ONLY_ARCHS: List[str] = ["kimi-vl-a3b"]
LM_ARCHS: List[str] = [k for k in _MODULES
                       if k not in XR_ARCHS and k not in TRAIN_ONLY_ARCHS]

# Assigned input-shape sets (LM family): name -> (seq_len, global_batch, kind)
SHAPES = {
    "train_4k": (4_096, 256, "train"),
    "prefill_32k": (32_768, 32, "prefill"),
    "decode_32k": (32_768, 128, "decode"),
    "long_500k": (524_288, 1, "decode"),
}


def _mod(name: str):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(f"repro.configs.{_MODULES[name]}")


def get_config(name: str) -> Union[ModelConfig, XRConfig]:
    return _mod(name).CONFIG


def get_smoke(name: str) -> Union[ModelConfig, XRConfig]:
    return _mod(name).SMOKE


def list_archs() -> List[str]:
    return list(_MODULES)


def cell_is_runnable(arch: str, shape: str) -> tuple[bool, str]:
    """Assignment skip rules for (arch x shape) dry-run cells."""
    cfg = get_config(arch)
    if not isinstance(cfg, ModelConfig):
        return False, "XR arch: evaluated on the edge-DSE plane, not the LM dry-run"
    if arch in TRAIN_ONLY_ARCHS:
        return False, "training-only arch: no decode path"
    if shape == "long_500k" and not cfg.sub_quadratic:
        return False, "long_500k skipped: pure full/windowed attention (see DESIGN §4)"
    return True, ""
