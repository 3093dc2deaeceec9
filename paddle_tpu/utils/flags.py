"""Global flag registry (reference: paddle/fluid/platform/flags.cc — 50
PADDLE_DEFINE_EXPORTED flags bridged to Python via __bootstrap__ and
set_flags/get_flags, pybind/global_value_getter_setter.cc).

TPU-native: a plain dict registry with FLAGS_* environment overrides applied
at import — every registered flag is settable via env exactly as in the
reference.
"""
from __future__ import annotations

import os
from typing import Any, Dict

_REGISTRY: Dict[str, Any] = {}


def define_flag(name: str, default, help_str: str = ""):
    env = os.environ.get("FLAGS_" + name)
    value = default
    if env is not None:
        if isinstance(default, bool):
            value = env.lower() in ("1", "true", "yes")
        elif isinstance(default, int):
            value = int(env)
        elif isinstance(default, float):
            value = float(env)
        else:
            value = env
    _REGISTRY[name] = value
    return value


def set_flags(flags: Dict[str, Any]):
    for k, v in flags.items():
        k = k[len("FLAGS_"):] if k.startswith("FLAGS_") else k
        _REGISTRY[k] = v


def fast_get(name: str):
    """Hot-path flag read: direct registry access, no dict building.
    Safe to cache the bound function — the registry dict is mutated in
    place by set_flags, never replaced."""
    return _REGISTRY.get(name)


def get_flags(names=None):
    if names is None:
        return dict(_REGISTRY)
    if isinstance(names, str):
        names = [names]
    out = {}
    for k in names:
        k2 = k[len("FLAGS_"):] if k.startswith("FLAGS_") else k
        out[k] = _REGISTRY.get(k2)
    return out


# -- core flags (the TPU-meaningful subset of flags.cc) ----------------------
define_flag("check_nan_inf", False,
            "check every op output for NaN/Inf (reference operator.cc:1252)")
define_flag("use_flash_attention", True, "route attention through Pallas")
define_flag("autotune", False,
            "time kernel variant/config candidates on first call per "
            "(shape, dtype, platform) key and pick the fastest "
            "(kernels/autotune.py); off = hand-tuned defaults / cached "
            "picks only.  Also settable via PADDLE_TPU_AUTOTUNE=1")
define_flag("autotune_samples", 5,
            "timing samples per autotune candidate (median is taken)")
define_flag("autotune_pin", "",
            "pin autotune candidates: 'family=variant[:k=v,...];...' — "
            "e.g. 'flash_fwd=bf16chain+iotafree:block_q=256'; wins over "
            "cache and tuning (env: PADDLE_TPU_AUTOTUNE_PIN)")
define_flag("benchmark", False, "sync after each op for timing")
define_flag("seed", 0, "global random seed")
define_flag("allocator_strategy", "xla", "memory allocator (XLA BFC)")
define_flag("tpu_matmul_precision", "default",
            "jax.default_matmul_precision for fp32 matmuls")
