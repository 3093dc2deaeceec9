"""A routed expert layer that is told which experts it holds.

``distributed/moe.py`` beside this is the capacity-based layer (top-1/top-2,
a dense (tokens, experts, capacity) dispatch that drops what overflows).
This one is dropless and sparse: the top k of all ``router_width`` experts,
grouped matrix products over the experts ``held`` here
(``kernels/grouped_matmul.py``), a shared expert.  A layer names its router
and its expert form:

* ``router="sigmoid"`` (DeepSeek-V3's, as Nemotron-H takes it): sigmoid
  scores with a selection bias and a routed scaling factor;
  ``router="softmax"`` (Qwen3-Next's): a float32 softmax over all the
  experts, the chosen probabilities renormalised, no bias, no factor;
* ``expert="relu2"``: ``down(relu(up(x))^2)``, two stacked weights an
  expert; ``expert="gated"``: ``down(silu(gate(x)) * up(x))``, three.  The
  shared expert has the same form, and with ``shared_gate`` its output is
  multiplied by ``sigmoid(x . w)`` a token.

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


class GatedMLP(Layer):
    """``down(silu(gate(x)) * up(x))``, no bias."""

    def __init__(self, hidden_size, intermediate_size):
        super().__init__()
        self.gate_proj = Linear(hidden_size, intermediate_size,
                                bias_attr=False)
        self.up_proj = Linear(hidden_size, intermediate_size,
                              bias_attr=False)
        self.down_proj = Linear(intermediate_size, hidden_size,
                                bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class TopKRouter(Layer):
    """The router's float32 weight (hidden, router_width) and, for the
    sigmoid router, its selection bias, a buffer
    (``e_score_correction_bias``: moved by a load balancer,
    not by the gradient; zero until one moves it).  The buffer is not
    persistable: nothing here moves it yet, so a checkpoint has nothing to
    keep, and a compiled step reads it as a constant."""

    def __init__(self, hidden_size, router_width, selection_bias=True):
        super().__init__()
        self.weight = self.create_parameter(
            (hidden_size, router_width),
            default_initializer=I.Normal(0.0, 0.02))
        self.weight.keep_fp32 = True
        if selection_bias:
            self.register_buffer("e_score_correction_bias",
                                 Tensor(np.zeros(router_width, np.float32)),
                                 persistable=False)


class HeldExperts(Layer):
    """The experts this chip holds, stacked: ``up_proj`` (held, hidden,
    width) and ``down_proj`` (held, width, hidden); gated experts have a
    ``gate_proj`` like ``up_proj`` too."""

    def __init__(self, held, hidden_size, intermediate_size, gated=False):
        super().__init__()
        init = I.Normal(0.0, 0.02)
        if gated:
            self.gate_proj = self.create_parameter(
                (held, hidden_size, intermediate_size),
                default_initializer=init)
        self.up_proj = self.create_parameter(
            (held, hidden_size, intermediate_size), default_initializer=init)
        self.down_proj = self.create_parameter(
            (held, intermediate_size, hidden_size), default_initializer=init)


class RoutedExperts(Layer):
    """x (b, s, hidden) -> (b, s, hidden): the held experts' part of a
    top-k routed layer plus the shared expert.

    ``held`` lists the ids, among ``router_width`` experts, of the ones
    whose weights live here (default: all of them); ``top_k`` experts a
    token are chosen among all ``router_width``.  ``router`` is
    ``"sigmoid"`` or ``"softmax"``, ``expert`` ``"relu2"`` or ``"gated"``
    (the module's docstring); ``shared_gate`` multiplies the shared
    expert's output by ``sigmoid(x . shared_gate)``."""

    _scope = _scopes.MOE

    def __init__(self, hidden_size, intermediate_size, router_width, top_k,
                 held=None, shared_intermediate_size=0,
                 routed_scaling_factor=1.0, router="sigmoid",
                 expert="relu2", shared_gate=False):
        super().__init__()
        if router not in ("sigmoid", "softmax") or expert not in (
                "relu2", "gated"):
            raise ValueError("router %r is neither sigmoid nor softmax, or "
                             "expert %r neither relu2 nor gated"
                             % (router, expert))
        self.held = tuple(range(router_width) if held is None else held)
        if len(set(self.held)) != len(self.held) or not all(
                0 <= e < router_width for e in self.held):
            raise ValueError("held experts %r are not distinct ids below "
                             "the router's width %d"
                             % (self.held, router_width))
        self.router_width, self.top_k = router_width, top_k
        self.routed_scaling_factor = routed_scaling_factor
        self.router, self.gated = router, expert == "gated"
        self.gate = TopKRouter(hidden_size, router_width,
                               selection_bias=router == "sigmoid")
        self.experts = HeldExperts(len(self.held), hidden_size,
                                   intermediate_size, self.gated)
        mlp = GatedMLP if self.gated else SquaredReLUMLP
        self.shared_experts = (
            mlp(hidden_size, shared_intermediate_size)
            if shared_intermediate_size else None)
        self.shared_gate = (
            self.create_parameter((hidden_size,),
                                  default_initializer=I.Normal(0.0, 0.02))
            if shared_gate and shared_intermediate_size else None)

    def forward(self, x):
        b, s, h = x.shape
        held, width, k = self.held, self.router_width, self.top_k
        # dropless: a step whose routing does not fit the usual launch
        # takes the worst case, tokens x min(k, held) rows, at once or,
        # where that is gigabytes, window by window
        usual = FE.usual_rows(b * s, k, len(held), width)
        FE.note_call("megablox" if kernel_path(usual) else "ragged_dot",
                     b * s, k, len(held), width, usual)

        def raw(a, router, bias, w_up, w_down, w_gate=None):
            flat = a.reshape(b * s, h)
            if self.router == "softmax":
                chosen, weights = FE.route_softmax_raw(flat, router, k)
            else:
                chosen, weights = FE.route_raw(flat, router, bias, k,
                                               self.routed_scaling_factor)
            part = FE.held_experts_raw(
                flat, FE.local_ids(chosen, held, width), weights, w_up,
                w_down, usual, w_gate)
            return part.astype(a.dtype).reshape(b, s, h)

        experts = self.experts
        out = call(raw, x, self.gate.weight,
                   getattr(self.gate, "e_score_correction_bias", None),
                   experts.up_proj, experts.down_proj,
                   *((experts.gate_proj,) if self.gated else ()),
                   name="routed_experts")
        if self.shared_experts is not None:
            shared = self.shared_experts(x)
            if self.shared_gate is not None:
                shared = call(FE.gate_shared_raw, shared, x,
                              self.shared_gate, name="shared_expert_gate")
            out = out + shared
        return out

