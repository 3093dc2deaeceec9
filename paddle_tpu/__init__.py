"""paddle_tpu — a TPU-native deep-learning framework.

Brand-new design (JAX/XLA/Pallas/pjit idiomatic) with the capability surface
of the PaddlePaddle reference snapshot (see SURVEY.md).  Eager Tensor/Layer
ergonomics over jax arrays with a tape autograd; compiled (`jit`) training
steps, pjit/GSPMD + shard_map parallelism, Pallas kernels for the hot ops.
"""
from __future__ import annotations

import time as _time

_T_IMPORT = _time.perf_counter()   # read at the bottom: process.import_seconds

__version__ = "0.1.0"

import jax as _jax

# paddle semantics need real int64 (labels, indices). float defaults stay
# f32 via our own dtype conversion in core.tensor._to_array.
_jax.config.update("jax_enable_x64", True)

# compile phases and cache verdicts are counted from here on, under the
# watched entry that pays them or under "(unwatched)"
from .observability import watchdog as _watchdog  # noqa: E402

_watchdog.listen()

from .core import (Generator, Parameter, Tensor, enable_grad,
                   get_rng_state, grad, is_grad_enabled, no_grad, seed,
                   set_grad_enabled, set_rng_state, to_tensor)
from .core.dtype import (bfloat16, bool_, complex64, complex128, float16,
                         float32, float64, get_default_dtype, int8, int16,
                         int32, int64, set_default_dtype, uint8)
from .core.tensor import is_tensor

from . import ops
from .ops import *  # noqa: F401,F403 — the paddle.* tensor-op surface
from .ops import random_ops as _random_ops
from .ops.random_ops import (bernoulli, multinomial, normal, rand, randint,
                             randn, randperm, standard_normal, uniform)

bool = bool_  # paddle.bool


def is_grad_enabled_():
    return is_grad_enabled()


# Subpackages (imported lazily enough to avoid cycles: nn imports ops only)
from . import nn            # noqa: E402
from . import optimizer     # noqa: E402
from . import autograd      # noqa: E402
from . import amp           # noqa: E402
from . import io            # noqa: E402
from . import jit           # noqa: E402
from . import static        # noqa: E402
from . import distributed   # noqa: E402
from . import vision        # noqa: E402
from . import metric        # noqa: E402
from . import distribution  # noqa: E402
from . import device        # noqa: E402
from . import framework     # noqa: E402
from . import utils         # noqa: E402
from . import incubate      # noqa: E402
from . import robustness    # noqa: E402
from . import fft           # noqa: E402
from . import signal        # noqa: E402
from . import linalg        # noqa: E402
from . import regularizer   # noqa: E402
from . import callbacks     # noqa: E402
from . import hub           # noqa: E402
from . import sysconfig     # noqa: E402
from . import tensor        # noqa: E402
from . import inference     # noqa: E402
from . import reader        # noqa: E402
from . import dataset       # noqa: E402
from . import compat        # noqa: E402
from .batch import batch    # noqa: E402
from . import sparse        # noqa: E402
from . import text          # noqa: E402
from . import onnx          # noqa: E402
from . import profiler      # noqa: E402
from . import hapi          # noqa: E402
from .hapi import Model, flops, summary  # noqa: E402
from .framework import load, save  # noqa: E402
from .utils.flags import get_flags, set_flags  # noqa: E402
from .nn import DataParallel  # noqa: E402
from .device import get_device, set_device  # noqa: E402
from .jit import to_static  # noqa: E402

Layer = nn.Layer

from .observability import registry as _obs_registry  # noqa: E402

_obs_registry.gauge("process.import_seconds").set(
    _time.perf_counter() - _T_IMPORT)
