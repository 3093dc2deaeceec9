"""A routed expert layer that is told which experts it holds.

``distributed/moe.py`` beside this is the capacity-based layer (top-1/top-2,
a dense (tokens, experts, capacity) dispatch that drops what overflows).
This one is dropless and sparse: sigmoid scores with a selection bias, the
top k of all ``router_width`` experts, a grouped matrix product over the
experts ``held`` here (``kernels/grouped_matmul.py``), squared-ReLU experts, a shared expert, a routed
scaling factor (DeepSeek-V3's router as Nemotron-H takes it).

The layer computes ``sum over (chosen and held) w_i expert_i(x) +
shared(x)``: under expert parallelism the parts of the other chips' experts
arrive by the exchange; on one chip there is none and the partial result is
the layer's output.  Import it from here (``paddle_tpu.nn`` does not).
"""
from __future__ import annotations

import numpy as np

from ... import ops
from ...core.dispatch import call
from ...core.tensor import Tensor
from ...kernels.grouped_matmul import kernel_path
from ...observability import scopes as _scopes
from .. import functional as F
from .. import initializer as I
from ..functional import experts as FE
from .common import Linear
from .layers import Layer


class SquaredReLUMLP(Layer):
    """``down(relu(up(x))^2)``, no gate, no bias."""

    def __init__(self, hidden_size, intermediate_size):
        super().__init__()
        self.up_proj = Linear(hidden_size, intermediate_size,
                              bias_attr=False)
        self.down_proj = Linear(intermediate_size, hidden_size,
                                bias_attr=False)

    def forward(self, x):
        return self.down_proj(ops.square(F.relu(self.up_proj(x))))


class TopKRouter(Layer):
    """The router's float32 weight (hidden, router_width) and its selection
    bias, a buffer (``e_score_correction_bias``: moved by a load balancer,
    not by the gradient; zero until one moves it).  The buffer is not
    persistable: nothing here moves it yet, so a checkpoint has nothing to
    keep, and a compiled step reads it as a constant."""

    def __init__(self, hidden_size, router_width):
        super().__init__()
        self.weight = self.create_parameter(
            (hidden_size, router_width),
            default_initializer=I.Normal(0.0, 0.02))
        self.weight.keep_fp32 = True
        self.register_buffer("e_score_correction_bias",
                             Tensor(np.zeros(router_width, np.float32)),
                             persistable=False)


class HeldExperts(Layer):
    """The experts this chip holds, stacked: ``up_proj`` (held, hidden,
    width) and ``down_proj`` (held, width, hidden)."""

    def __init__(self, held, hidden_size, intermediate_size):
        super().__init__()
        init = I.Normal(0.0, 0.02)
        self.up_proj = self.create_parameter(
            (held, hidden_size, intermediate_size), default_initializer=init)
        self.down_proj = self.create_parameter(
            (held, intermediate_size, hidden_size), default_initializer=init)


class RoutedExperts(Layer):
    """x (b, s, hidden) -> (b, s, hidden): the held experts' part of a
    top-k routed layer plus the shared expert.

    ``held`` lists the ids, among ``router_width`` experts, of the ones
    whose weights live here (default: all of them); ``top_k`` experts a
    token are chosen among all ``router_width``."""

    _scope = _scopes.MOE

    def __init__(self, hidden_size, intermediate_size, router_width, top_k,
                 held=None, shared_intermediate_size=0,
                 routed_scaling_factor=1.0):
        super().__init__()
        self.held = tuple(range(router_width) if held is None else held)
        if len(set(self.held)) != len(self.held) or not all(
                0 <= e < router_width for e in self.held):
            raise ValueError("held experts %r are not distinct ids below "
                             "the router's width %d"
                             % (self.held, router_width))
        self.router_width, self.top_k = router_width, top_k
        self.routed_scaling_factor = routed_scaling_factor
        self.gate = TopKRouter(hidden_size, router_width)
        self.experts = HeldExperts(len(self.held), hidden_size,
                                   intermediate_size)
        self.shared_experts = (
            SquaredReLUMLP(hidden_size, shared_intermediate_size)
            if shared_intermediate_size else None)

    def forward(self, x):
        b, s, h = x.shape
        held, width, k = self.held, self.router_width, self.top_k
        # dropless: a step whose routing does not fit the usual launch
        # takes the worst case, tokens x min(k, held) rows
        usual = FE.usual_rows(b * s, k, len(held), width)
        FE.note_call("megablox" if kernel_path(usual) else "ragged_dot",
                     b * s, k, len(held), width, usual)

        def raw(a, router, bias, w_up, w_down):
            flat = a.reshape(b * s, h)
            chosen, weights = FE.route_raw(flat, router, bias, k,
                                           self.routed_scaling_factor)
            part = FE.held_experts_raw(
                flat, FE.local_ids(chosen, held, width), weights, w_up,
                w_down, usual)
            return part.astype(a.dtype).reshape(b, s, h)

        out = call(raw, x, self.gate.weight,
                   self.gate.e_score_correction_bias, self.experts.up_proj,
                   self.experts.down_proj, name="routed_experts")
        if self.shared_experts is not None:
            out = out + self.shared_experts(x)
        return out
