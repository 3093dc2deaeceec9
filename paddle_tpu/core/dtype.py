"""Dtype registry.

The reference exposes paddle dtypes through ``paddle.float32`` etc. and a
VarType enum (reference: paddle/fluid/framework/framework.proto:117).  Here a
dtype is simply a ``jnp.dtype``; this module provides the canonical aliases,
name normalisation and the default-dtype switch
(reference: python/paddle/framework/framework.py set_default_dtype).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

bool_ = jnp.bool_
uint8 = jnp.uint8
int8 = jnp.int8
int16 = jnp.int16
int32 = jnp.int32
int64 = jnp.int64
float16 = jnp.float16
bfloat16 = jnp.bfloat16
float32 = jnp.float32
float64 = jnp.float64
complex64 = jnp.complex64
complex128 = jnp.complex128

_ALIASES = {
    "bool": bool_,
    "uint8": uint8,
    "int8": int8,
    "int16": int16,
    "int32": int32,
    "int64": int64,
    "float16": float16,
    "bfloat16": bfloat16,
    "bf16": bfloat16,
    "fp16": float16,
    "float32": float32,
    "fp32": float32,
    "float64": float64,
    "float": float32,
    "double": float64,
    "int": int32,
    "complex64": complex64,
    "complex128": complex128,
}

_default_dtype = [np.dtype("float32")]


def set_default_dtype(d):
    _default_dtype[0] = convert_dtype(d)


def get_default_dtype():
    return _default_dtype[0]


def convert_dtype(d):
    """Normalise any dtype spec (str alias, np/jnp dtype, python type) to np.dtype."""
    if d is None:
        return None
    if isinstance(d, str):
        if d in _ALIASES:
            return np.dtype(_ALIASES[d])
        return np.dtype(d)
    return np.dtype(d)


def is_floating(dtype) -> bool:
    return jnp.issubdtype(np.dtype(dtype), jnp.floating)


def is_complex(dtype) -> bool:
    return jnp.issubdtype(np.dtype(dtype), jnp.complexfloating)


def is_inexact(dtype) -> bool:
    return is_floating(dtype) or is_complex(dtype)


def is_integer(dtype) -> bool:
    return jnp.issubdtype(np.dtype(dtype), jnp.integer)


def x64_scope(enable: bool):
    """``jax.enable_x64(enable)`` as a context manager.

    Pallas kernels trace under ``x64_scope(False)`` because Mosaic cannot
    lower i64/f64 even though the global x64 mode is on.
    """
    import jax
    return jax.enable_x64(enable)
