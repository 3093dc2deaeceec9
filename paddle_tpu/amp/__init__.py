"""Automatic mixed precision (reference surface: python/paddle/amp/ —
auto_cast O1/O2 lists at auto_cast.py:21, GradScaler at grad_scaler.py:26).

TPU-native policy: bf16 is the default mixed dtype and needs NO loss scaling
(full fp32 exponent range), so ``GradScaler`` with bf16 is an API-compatible
pass-through; dynamic loss scaling is implemented for explicit fp16 use.

Mechanism: ``auto_cast`` installs a global amp state consulted by the op
dispatcher — white-listed ops (matmul/conv: the MXU ops) cast fp32 inputs to
the amp dtype; black-listed ops stay fp32.  Under O2, ``decorate`` casts the
model's parameters themselves.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..robustness.faultpoints import declare as _declare, faultpoint

_declare("amp.found_inf",
         "override the GradScaler's found-inf verdict (ForceFoundInf "
         "simulates an fp16 overflow step without overflow-scale grads)")

# Reference O1 lists (auto_cast.py): ops that are numerically safe + MXU-bound
WHITE_LIST = {"matmul", "bmm", "mm", "conv1d", "conv2d", "conv3d", "linear",
              "einsum", "mv", "addmm"}
BLACK_LIST = {"exp", "log", "log2", "log10", "log1p", "pow", "square",
              "softmax_with_cross_entropy", "cross_entropy", "cumsum",
              "logsumexp", "norm", "mean", "sum", "var", "std",
              "layer_norm", "batch_norm", "rsqrt", "softmax"}

_amp_state = {"enable": False, "dtype": np.dtype("float32"), "level": "O1",
              "white": WHITE_LIST, "black": BLACK_LIST}


def amp_state():
    return _amp_state


def amp_cast_inputs(op_name, arrays):
    """Called by the dispatcher: cast fp32 inputs of white-listed ops."""
    st = _amp_state
    if not st["enable"]:
        return arrays
    if op_name in st["black"]:
        return arrays
    level = st["level"]
    if level == "O2" or op_name in st["white"]:
        dt = st["dtype"]
        out = []
        for a in arrays:
            if hasattr(a, "dtype") and a.dtype == jnp.float32:
                out.append(a.astype(dt))
            else:
                out.append(a)
        return out
    return arrays


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16"):
    """reference parity: paddle.amp.auto_cast (auto_cast.py:21)."""
    from ..core.dtype import convert_dtype
    prev = dict(_amp_state)
    _amp_state["enable"] = enable
    _amp_state["dtype"] = convert_dtype(dtype)
    _amp_state["level"] = level
    if custom_white_list:
        _amp_state["white"] = WHITE_LIST | set(custom_white_list)
    if custom_black_list:
        _amp_state["black"] = BLACK_LIST | set(custom_black_list)
    try:
        yield
    finally:
        _amp_state.update(prev)


amp_guard = auto_cast


def decorate(models, optimizers=None, level="O2", dtype="bfloat16",
             master_weight=None, save_dtype=None):
    """reference parity: paddle.amp.decorate (auto_cast.py:81) — O2 casts
    parameters to the amp dtype (master fp32 weights are kept by optimizers
    whose slots are fp32, which ours are).  Norm layers stay fp32, and so
    does any parameter marked ``keep_fp32`` (``RMSNorm`` marks its gain; a
    model marks a decay rate, a step-size bias, a router)."""
    from ..nn.layer.norm import _BatchNormBase, LayerNorm

    single = not isinstance(models, (list, tuple))
    model_list = [models] if single else list(models)
    if level == "O2":
        for m in model_list:
            for layer in m.sublayers(include_self=True):
                if isinstance(layer, (_BatchNormBase, LayerNorm)):
                    continue  # keep norms fp32 (reference keep_batch_norm_fp32)
                for p in layer._parameters.values():
                    if (p is not None and not p.keep_fp32
                            and p.dtype == np.dtype("float32")):
                        p._array = p._array.astype(dtype)
    if optimizers is None:
        return models if single else model_list
    return (models if single else model_list), optimizers


class GradScaler:
    """Dynamic loss scaling (reference: grad_scaler.py:26 over
    fluid/dygraph/amp/loss_scaler.py:40 AmpScaler).

    With bf16 (TPU default) scaling is unnecessary — ``enable=False`` makes
    every method a pass-through, and that is the recommended mode.
    """

    def __init__(self, enable=True, init_loss_scaling=2.0 ** 15,
                 incr_ratio=2.0, decr_ratio=0.5, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling)
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every = incr_every_n_steps
        self._decr_every = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False
        self._last_skipped = False
        self._already_unscaled = set()

    def scale(self, var):
        if not self._enable:
            return var
        return var * self._scale

    def unscale_(self, optimizer):
        if not self._enable:
            return
        if id(optimizer) in self._already_unscaled:
            return  # never divide by the scale twice (explicit + step())
        self._already_unscaled.add(id(optimizer))
        inv = 1.0 / self._scale
        found_inf = False
        for p in optimizer._parameter_list:
            if p.grad is not None:
                arr = p.grad._array * inv
                finite = bool(jnp.all(jnp.isfinite(arr)))
                if not finite:
                    found_inf = True
                p.grad._array = arr
        self._found_inf = found_inf

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            self._last_skipped = False
            return
        self.unscale_(optimizer)   # no-op if the user already unscaled
        ctx = faultpoint("amp.found_inf", found_inf=self._found_inf)
        if ctx is not None:
            self._found_inf = bool(ctx["found_inf"])
        # recorded BEFORE _update resets the flag: DivergenceSentinel reads
        # this to tell "the fp16 gate already skipped the poisoned update"
        # (params intact — no rewind needed) from a real divergence
        self._last_skipped = self._found_inf
        if self._found_inf:
            from ..observability import registry as _metrics
            _metrics.counter("train.amp_skipped_steps").inc()
        else:
            optimizer.step()
        self._already_unscaled.discard(id(optimizer))
        self._update()

    def minimize(self, optimizer, scaled_loss):
        self.step(optimizer)

    def update(self):
        pass  # folded into step() as in the reference eager path

    def _update(self):
        if not self._dynamic:
            self._found_inf = False
            return
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
        else:
            self._good_steps += 1
            self._bad_steps = 0
            if self._good_steps >= self._incr_every:
                self._scale *= self._incr_ratio
                self._good_steps = 0
        self._found_inf = False

    @property
    def last_step_skipped(self) -> bool:
        """True iff the most recent ``step()`` skipped the optimizer update
        because non-finite gradients were found (the fp16 overflow path)."""
        return self._last_skipped

    def is_enable(self):
        return self._enable

    def is_use_dynamic_loss_scaling(self):
        return self._dynamic

    def get_loss_scaling(self):
        return self._scale

    def set_init_loss_scaling(self, v):
        self._scale = float(v)

    def state_dict(self):
        return {"scale": self._scale, "good_steps": self._good_steps,
                "bad_steps": self._bad_steps, "enable": self._enable}

    def load_state_dict(self, sd):
        self._scale = sd["scale"]
        self._good_steps = sd["good_steps"]
        self._bad_steps = sd["bad_steps"]

    set_state_dict = load_state_dict
