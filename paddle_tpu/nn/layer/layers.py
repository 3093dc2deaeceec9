"""nn.Layer — the module base class.

API parity target: python/paddle/fluid/dygraph/layers.py:83 (Layer), with
parameters(), named_parameters(), sublayers(), state_dict(), buffers,
forward/backward hooks, train/eval, apply, to().  TPU-native addition: every
Layer is also usable *functionally* — ``layer.functional_state()`` exports the
parameter pytree and ``paddle_tpu.jit.functional_call`` runs forward against
an externally supplied pytree, which is what the compiled/pjit training path
uses.
"""
from __future__ import annotations

import collections
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

from ...core import dtype as _dt
from ...core.tensor import Parameter, Tensor
from ...observability import scopes as _scopes


class HookRemoveHelper:
    next_id = 0

    def __init__(self, hooks):
        self._hooks = hooks
        self._id = HookRemoveHelper.next_id
        HookRemoveHelper.next_id += 1

    def remove(self):
        self._hooks.pop(self._id, None)


class Layer:
    #: the role this layer's work carries in a compiled program's trace
    #: (a name of ``observability.scopes.VOCABULARY``); None adds no scope.
    #: A model names its blocks by setting this on their classes
    _scope: Optional[str] = None

    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = _dt.convert_dtype(dtype)
        self._parameters: Dict[str, Parameter] = collections.OrderedDict()
        self._sub_layers: Dict[str, "Layer"] = collections.OrderedDict()
        self._buffers: Dict[str, Tensor] = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = collections.OrderedDict()
        self._forward_post_hooks = collections.OrderedDict()
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # -- construction -------------------------------------------------------
    def create_parameter(self, shape, dtype=None, attr=None, is_bias=False,
                         default_initializer=None):
        from .. import initializer as I
        dtype = _dt.convert_dtype(dtype) or self._dtype
        init = None
        name = None
        if attr is not None and attr is not False:
            init = getattr(attr, "initializer", None)
            name = getattr(attr, "name", None)
        if init is None:
            init = default_initializer or (I.Constant(0.0) if is_bias
                                           else I.XavierNormal())
        arr = init(tuple(int(s) for s in shape), dtype)
        p = Parameter(arr, dtype=dtype, name=name)
        return p

    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        else:
            self._non_persistable_buffer_names.discard(name)
        return tensor

    # -- attribute routing --------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ first")
            params[name] = value
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            object.__setattr__(self, name, value)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ first")
            layers[name] = value
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            object.__setattr__(self, name, value)
        else:
            if params is not None and name in params and value is None:
                params.pop(name)
            if layers is not None and name in layers and value is None:
                layers.pop(name)
            if buffers is not None and name in buffers:
                if isinstance(value, Tensor):
                    buffers[name] = value
                elif value is None:
                    buffers.pop(name)
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        # only called when normal lookup fails
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    # -- traversal ----------------------------------------------------------
    def named_sublayers(self, prefix="", include_self=False, layers_set=None
                        ) -> Iterator[Tuple[str, "Layer"]]:
        if layers_set is None:
            layers_set = set()
        if include_self and id(self) not in layers_set:
            layers_set.add(id(self))
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None or id(layer) in layers_set:
                continue
            layers_set.add(id(layer))
            p = prefix + ("." if prefix else "") + name
            yield p, layer
            yield from layer.named_sublayers(prefix=p, include_self=False,
                                             layers_set=layers_set)

    def sublayers(self, include_self=False) -> List["Layer"]:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return iter(self._sub_layers.values())

    def named_children(self):
        return iter(self._sub_layers.items())

    def named_parameters(self, prefix="", include_sublayers=True
                         ) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for layer_name, layer in self.named_sublayers(prefix=prefix,
                                                      include_self=True):
            for pname, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (layer_name + ("." if layer_name else "") + pname, p)
            if not include_sublayers:
                break

    def parameters(self, include_sublayers=True) -> List[Parameter]:
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="") -> Iterator[Tuple[str, Tensor]]:
        seen = set()
        for layer_name, layer in self.named_sublayers(prefix=prefix,
                                                      include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (layer_name + ("." if layer_name else "") + bname, b)

    def buffers(self) -> List[Tensor]:
        return [b for _, b in self.named_buffers()]

    def apply(self, fn):
        for layer in self.sublayers(include_self=True):
            fn(layer)
        return self

    # -- modes --------------------------------------------------------------
    def train(self):
        for layer in self.sublayers(include_self=True):
            layer.training = True
        return self

    def eval(self):
        for layer in self.sublayers(include_self=True):
            layer.training = False
        return self

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        h = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[h._id] = hook
        return h

    def register_forward_post_hook(self, hook):
        h = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[h._id] = hook
        return h

    # -- call ---------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        if self._scope is None:
            outputs = self.forward(*inputs, **kwargs)
        else:
            with _scopes.scope(self._scope):
                outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            res = hook(self, inputs, outputs)
            if res is not None:
                outputs = res
        return outputs

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = collections.OrderedDict() if destination is None else destination
        for name, p in self.named_parameters(prefix=structured_name_prefix):
            dest[name] = p
        for lname, layer in self.named_sublayers(prefix=structured_name_prefix,
                                                 include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or bname in layer._non_persistable_buffer_names:
                    continue
                dest[lname + ("." if lname else "") + bname] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, t in own.items():
            if name in state_dict:
                v = state_dict[name]
                arr = v._array if isinstance(v, Tensor) else np.asarray(v)
                t.set_value(Tensor(arr).astype(t.dtype))
            else:
                missing.append(name)
        for name in state_dict:
            if name not in own:
                unexpected.append(name)
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # -- dtype / device movement -------------------------------------------
    def to(self, device=None, dtype=None, blocking=True):
        if dtype is not None:
            self._convert(dtype)
        return self

    def astype(self, dtype):
        self._convert(dtype)
        return self

    def _convert(self, dtype):
        dtype = _dt.convert_dtype(dtype)
        for p in self.parameters():
            if _dt.is_floating(p.dtype):
                p._array = p._array.astype(dtype)
        for b in self.buffers():
            if _dt.is_floating(b.dtype):
                b._array = b._array.astype(dtype)
        self._dtype = dtype

    def float(self):
        return self.astype("float32")

    def bfloat16(self):
        return self.astype("bfloat16")

    # -- functional bridge --------------------------------------------------
    def functional_state(self) -> Dict[str, object]:
        """Export {name: jax array} for params + persistable buffers — the
        pytree the compiled path feeds to functional_call."""
        return {name: t._array for name, t in self.state_dict().items()}

    def load_functional_state(self, tree: Dict[str, object]):
        sd = self.state_dict()
        for name, arr in tree.items():
            if name in sd:
                sd[name]._array = arr

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def full_name(self):
        return self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, layer in self._sub_layers.items():
            rep = repr(layer).split("\n")
            rep = [rep[0]] + ["  " + r for r in rep[1:]]
            lines.append(f"  ({name}): " + "\n".join(rep))
        main = f"{self.__class__.__name__}({extra}"
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        if idx < 0:
            idx += len(self)
        return self._sub_layers[str(idx)]

    def __setitem__(self, idx, layer):
        self._sub_layers[str(idx)] = layer

    def append(self, layer):
        self.add_sublayer(str(len(self)), layer)
        return self

    def insert(self, index, layer):
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self._sub_layers[str(i)] = l

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], collections.OrderedDict):
            for name, l in layers[0].items():
                self.add_sublayer(name, l)
        else:
            for i, l in enumerate(layers):
                if isinstance(l, (list, tuple)) and len(l) == 2 and isinstance(l[0], str):
                    self.add_sublayer(l[0], l[1])
                else:
                    self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return Sequential(*list(self._sub_layers.values())[idx])
        return list(self._sub_layers.values())[idx]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def forward(self, x):
        for layer in self._sub_layers.values():
            x = layer(x)
        return x


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())

    def __getitem__(self, idx):
        return self._parameters[str(idx)]

    def append(self, p):
        self.add_parameter(str(len(self)), p)
        return self
