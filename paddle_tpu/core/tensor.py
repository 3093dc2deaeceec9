"""The eager Tensor.

Design (TPU-native rethink of the reference's eager Tensor):

* A ``Tensor`` is a thin wrapper around a ``jax.Array`` (or a jax tracer while
  inside a ``jit`` trace).  All math routes through ``jax.numpy`` so the same
  op code serves the eager path and the compiled (``to_static``/``pjit``) path.
* Autograd is a dynamic graph of ``GradNode`` objects built per-op via
  ``jax.vjp`` closures — the structural analogue of the reference's eager
  autograd (reference: paddle/fluid/eager/grad_node_info.h:90 GradNodeBase,
  autograd_meta.h AutogradMeta), with ``jax.vjp`` replacing generated grad
  kernels.
* ``stop_gradient`` defaults to True for plain tensors and False for
  ``Parameter``s, matching reference semantics
  (reference: python/paddle/fluid/framework.py Parameter).

The fast training path never walks this tape: ``paddle_tpu.jit.to_static`` /
``TrainStep`` trace the same ops under ``jax.grad`` where the tape is disabled.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as _dtype_mod
from .grad_mode import is_grad_enabled, no_grad

Array = Any


class GradNode:
    """One recorded op in the autograd graph.

    Holds the ``jax.vjp`` pullback for the op, strong references to the input
    tensors (the analogue of the reference's TensorWrapper saved-tensors,
    reference: paddle/fluid/eager/tensor_wrapper.h) and the output avals so
    missing cotangents can be zero-filled.
    """

    __slots__ = ("vjp_fn", "inputs", "out_avals", "name", "out_treedef")

    def __init__(self, vjp_fn, inputs, out_avals, name, out_treedef=None):
        self.vjp_fn = vjp_fn
        self.inputs = inputs          # list[Tensor] — differentiable inputs, in vjp order
        self.out_avals = out_avals    # list[(shape, dtype)] per output position
        self.name = name
        self.out_treedef = out_treedef

    def __repr__(self):
        return f"<GradNode {self.name} n_in={len(self.inputs)} n_out={len(self.out_avals)}>"


def _to_array(data, dtype=None):
    if isinstance(data, Tensor):
        arr = data._array
        if dtype is not None:
            arr = arr.astype(dtype)
        return arr
    if isinstance(data, (jnp.ndarray, jax.Array)) or hasattr(data, "aval"):
        return data if dtype is None else data.astype(dtype)
    if isinstance(data, np.ndarray):
        if dtype is None and data.dtype == np.float64:
            dtype = _dtype_mod.get_default_dtype()
        return jnp.asarray(data, dtype=dtype)
    if isinstance(data, (bool, int, float, complex)):
        if dtype is None:
            if isinstance(data, bool):
                dtype = np.dtype("bool")
            elif isinstance(data, int):
                dtype = np.dtype("int64")
            elif isinstance(data, float):
                dtype = _dtype_mod.get_default_dtype()
            else:
                dtype = np.dtype("complex64")
        return jnp.asarray(data, dtype=dtype)
    if isinstance(data, (list, tuple)):
        arr = np.asarray(data)
        if dtype is None and arr.dtype == np.float64:
            dtype = _dtype_mod.get_default_dtype()
        return jnp.asarray(arr, dtype=dtype)
    return jnp.asarray(data, dtype=dtype)


class Tensor:
    __slots__ = ("_array", "_stop_gradient", "_grad_node", "_out_index",
                 "grad", "name", "_backward_hooks", "persistable", "__weakref__")

    # let Tensor win against numpy array in mixed binary ops
    __array_priority__ = 100

    def __init__(self, data, dtype=None, stop_gradient=True, name=None):
        dtype = _dtype_mod.convert_dtype(dtype)
        self._array = _to_array(data, dtype)
        self._stop_gradient = bool(stop_gradient)
        self._grad_node: Optional[GradNode] = None
        self._out_index = 0
        self.grad: Optional[Tensor] = None
        self.name = name
        self._backward_hooks = None
        self.persistable = False

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self):
        return list(self._array.shape)

    @property
    def ndim(self):
        return self._array.ndim

    # paddle alias
    @property
    def dim(self):
        return self._array.ndim

    @property
    def size(self):
        return int(np.prod(self._array.shape)) if self._array.shape else 1

    @property
    def dtype(self):
        return np.dtype(self._array.dtype)

    @property
    def T(self):
        from .. import ops
        return ops.t(self)

    @property
    def mT(self):
        from .. import ops
        return ops.matrix_transpose(self)

    @property
    def stop_gradient(self):
        return self._stop_gradient

    @stop_gradient.setter
    def stop_gradient(self, value):
        self._stop_gradient = bool(value)

    @property
    def is_leaf(self):
        return self._grad_node is None

    @property
    def place(self):
        devs = getattr(self._array, "devices", None)
        if devs is None:
            return "traced"
        try:
            return str(next(iter(self._array.devices())))
        except Exception:
            return "traced"

    def numpy(self):
        return np.asarray(self._array)

    def item(self, *args):
        return self.numpy().item(*args)

    def tolist(self):
        return self.numpy().tolist()

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._array.shape[0]

    def __iter__(self):
        # explicit __iter__ is REQUIRED: without it Python falls back to
        # the __getitem__ protocol with ever-growing indices, and jax's
        # clamping gather never raises IndexError -> infinite loop on any
        # eager `for row in tensor` (reference tensors iterate rows)
        if self.ndim == 0:
            raise TypeError("iteration over a 0-d tensor")
        return (self[i] for i in range(self._array.shape[0]))

    def __bool__(self):
        return bool(self._array)

    def __int__(self):
        return int(self._array)

    def __float__(self):
        return float(self._array)

    def __index__(self):
        return int(self._array)

    def __hash__(self):
        return id(self)

    def __repr__(self):
        grad_part = "" if self._stop_gradient else ", stop_gradient=False"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}"
                f"{grad_part},\n       {np.asarray(self._array) if not self._is_traced() else self._array!r})")

    def _is_traced(self):
        return not isinstance(self._array, (np.ndarray,)) and not hasattr(self._array, "devices")

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        """Run reverse accumulation from this tensor.

        Reference analogue: egr::Backward (paddle/fluid/eager/backward.cc:797).
        """
        from .engine import run_backward
        run_backward([self], [grad_tensor], retain_graph=retain_graph)

    def clear_grad(self):
        self.grad = None

    def clear_gradient(self, set_to_zero=False):
        if set_to_zero and self.grad is not None:
            self.grad = Tensor(jnp.zeros_like(self.grad._array))
        else:
            self.grad = None

    def register_hook(self, hook):
        """Register a gradient hook; returns a removable handle.

        Reference analogue: egr::utils RegisterGradientHookForTensor /
        VarBase._register_grad_hook.
        """
        if self._backward_hooks is None:
            self._backward_hooks = {}
        hid = len(self._backward_hooks)
        self._backward_hooks[hid] = hook
        tensor = self

        class _Handle:
            def remove(self):
                tensor._backward_hooks.pop(hid, None)

        return _Handle()

    def detach(self):
        t = Tensor(self._array, stop_gradient=True)
        t.name = self.name
        return t

    def detach_(self):
        self._grad_node = None
        self._stop_gradient = True
        return self

    def clone(self):
        from .. import ops
        return ops.assign(self)

    # -- mutation (leaf-only, used by optimizers / state loading) -----------
    def set_value(self, value):
        arr = _to_array(value)
        if tuple(arr.shape) != tuple(self._array.shape):
            raise ValueError(
                f"set_value shape mismatch: {arr.shape} vs {self._array.shape}")
        self._array = arr.astype(self._array.dtype)
        return self

    def copy_(self, other):
        return self.set_value(other)

    def _replace_array(self, arr):
        """Internal: swap the underlying buffer (optimizer fast path)."""
        self._array = arr
        return self

    def astype(self, dtype):
        from .. import ops
        return ops.cast(self, dtype)

    def cast(self, dtype):
        return self.astype(dtype)

    def to(self, *args, **kwargs):
        # minimal: dtype and/or device
        dtype = kwargs.get("dtype")
        device = kwargs.get("device")
        for a in args:
            if isinstance(a, str) and (a in _dtype_mod._ALIASES or "int" in a or "float" in a or "bool" in a):
                dtype = a
            else:
                device = a
        out = self
        if dtype is not None:
            out = out.astype(dtype)
        if device is not None:
            arr = jax.device_put(out._array, device if not isinstance(device, str) else _resolve_device(device))
            out = Tensor(arr, stop_gradient=out.stop_gradient)
        return out

    def cpu(self):
        return Tensor(np.asarray(self._array), stop_gradient=self._stop_gradient)

    def pin_memory(self):
        return self

    def cuda(self, *a, **k):  # API-compat: "cuda" == accelerator
        return self

    # elementwise/methods are attached by paddle_tpu.ops.methods at import time


class Parameter(Tensor):
    """A trainable tensor (reference: python/paddle/fluid/framework.py Parameter)."""

    __slots__ = ("trainable", "optimize_attr", "regularizer", "is_distributed", "pspec",
                 "keep_fp32")

    _param_counter = [0]

    def __init__(self, data, dtype=None, name=None, trainable=True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name)
        if name is None:
            Parameter._param_counter[0] += 1
            self.name = f"param_{Parameter._param_counter[0]}"
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.is_distributed = False
        self.pspec = None  # optional jax PartitionSpec annotation
        # amp.decorate(level="O2") leaves a marked parameter in float32 (a
        # decay rate, a router: what a bf16 rounding changes in kind)
        self.keep_fp32 = False
        self.persistable = True

    @property
    def trainable_(self):
        return self.trainable

    def __repr__(self):
        return "Parameter " + super().__repr__()


def _resolve_device(name: str):
    """'cpu', 'tpu', 'tpu:2', ... -> the jax device.  'gpu'/'cuda'/
    'accelerator'/'xla' mean "the accelerator" as in reference scripts and
    resolve to the default backend; 'tpu' means a TPU, and a process that
    has none raises instead of handing back whatever device 0 is."""
    kind, _, idx = name.lower().partition(":")
    if kind in ("gpu", "cuda", "accelerator", "xla"):
        devices = jax.devices()
    elif kind == "tpu":
        if jax.default_backend() != "tpu":
            raise RuntimeError(
                "device %r requested but this process has no TPU (jax "
                "backend: %s)" % (name, jax.default_backend()))
        devices = jax.devices("tpu")
    elif kind == "cpu":
        devices = jax.devices("cpu")
    else:
        raise ValueError("unknown device %r" % name)
    return devices[int(idx) if idx else 0]


def is_tensor(x) -> bool:
    return isinstance(x, Tensor)


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor equivalent."""
    t = Tensor(data, dtype=dtype, stop_gradient=stop_gradient)
    if place is not None:
        t = t.to(place)
        t.stop_gradient = stop_gradient
    return t
