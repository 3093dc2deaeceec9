"""Qwen3-Next — hybrid decoder of Gated DeltaNet linear-attention layers and
output-gated softmax attention, every layer followed by a routed expert
layer (``model_type: qwen3_next``).

A layer is ``x <- x + mixer(N(x))`` then ``x <- x + experts(N(x))`` with
``N`` a zero-centred RMSNorm (``x / rms(x) * (1 + w)``).  Layer i is full
attention where ``(i + 1) % full_attention_interval == 0`` and Gated
DeltaNet otherwise (``layer_types`` overrides the interval):

* :class:`GatedDeltaNet` — ``[q | k | v | z] = in_proj_qkvz(x)``, ``[b | a]
  = in_proj_ba(x)``; a causal depthwise convolution and SiLU over ``[q | k |
  v]``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; q
  and k L2-normalised per head; the gated delta rule
  (``nn.functional.linear_attn``), each key head serving ``value heads /
  key heads`` value heads; ``out_proj(RMSNorm_head(o) * w * silu(z))``;
* :class:`GatedAttention` — ``[q | gate] = q_proj(x)`` a head; zero-centred
  RMSNorm over each head of q and k; rotary on the first ``partial_rotary
  _factor`` of a head's lanes; causal grouped-query attention through the
  flash kernels; ``o_proj(attn * sigmoid(gate))``;
* the expert layer — ``nn.layer.experts.RoutedExperts`` with the softmax
  router and gated experts, told which experts it holds, and a shared
  expert multiplied by ``sigmoid(x . w)``.

Then a final norm and an untied head.  Import it from here;
``paddle_tpu.models`` does not (a process that trains GPT-2 pays nothing
for it).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import ops
from ..core.dispatch import call
from ..nn import ParamAttr
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.functional import linear_attn as FL
from ..nn.functional import ssm as FS
from ..nn.functional.norm import rms_norm_raw
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.experts import RoutedExperts
from ..nn.layer.layers import Layer, LayerList
from ..nn.layer.norm import RMSNorm
from ..observability import scopes as _scopes
from .gpt import GPTPretrainingCriterion as Qwen3NextPretrainingCriterion

__all__ = ["Qwen3NextConfig", "Qwen3NextForCausalLM",
           "Qwen3NextPretrainingCriterion"]

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    # one of LINEAR / FULL a layer; default: by the interval
    layer_types: Optional[Tuple[str, ...]] = None
    # Gated DeltaNet
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    chunk_size: int = 64
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    # gated attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    # experts: ``num_experts`` are held here, ``held_experts`` says which of
    # the router's ``router_width`` (default: all, in order)
    num_experts: int = 512
    router_width: Optional[int] = None
    held_experts: Optional[Tuple[int, ...]] = None
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # the kinds of layer (of ``layer_types``' names) that are a
    # jax.checkpoint in training; none by default
    recompute: Tuple[str, ...] = ()

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                FULL if (i + 1) % self.full_attention_interval == 0
                else LINEAR for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        self.recompute = tuple(self.recompute)
        if (len(self.layer_types) != self.num_hidden_layers
                or set(self.layer_types + self.recompute) - {LINEAR, FULL}):
            raise ValueError(
                "layer_types %r does not name num_hidden_layers = %d kinds "
                "of %r, or recompute %r names another"
                % (self.layer_types, self.num_hidden_layers,
                   (LINEAR, FULL), self.recompute))
        if self.router_width is None:
            self.router_width = self.num_experts
        if self.held_experts is None:
            self.held_experts = tuple(range(self.num_experts))
        self.held_experts = tuple(self.held_experts)
        if len(self.held_experts) != self.num_experts:
            raise ValueError("held_experts %r does not list num_experts = "
                             "%d ids" % (self.held_experts,
                                         self.num_experts))

    @classmethod
    def tiny(cls, **kw):  # for tests
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            full_attention_interval=2, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, chunk_size=16, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            shared_expert_intermediate_size=32), **kw})


class GatedDeltaNet(Layer):
    """The linear-attention mixer (the module's docstring has its
    equations).  The projections' columns are ``[q | k | v | z]`` and ``[b |
    a]``, heads in order; the convolution's taps are stored (taps,
    channels) and it has no bias."""

    _scope = _scopes.LINEAR_ATTN

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        c = config
        self.config = c
        self.key_dim = c.linear_num_key_heads * c.linear_key_head_dim
        self.value_dim = c.linear_num_value_heads * c.linear_value_head_dim
        heads = c.linear_num_value_heads
        self.in_proj_qkvz = Linear(
            c.hidden_size, 2 * self.key_dim + 2 * self.value_dim,
            weight_attr=_normal(c.initializer_range), bias_attr=False)
        self.in_proj_ba = Linear(
            c.hidden_size, 2 * heads,
            weight_attr=_normal(c.initializer_range), bias_attr=False)
        # taps first: tap j multiplies x_{t-(k-1)+j}
        self.conv1d_weight = self.create_parameter(
            (c.linear_conv_kernel_dim, 2 * self.key_dim + self.value_dim),
            default_initializer=I.Uniform(-0.5, 0.5))
        # decay rate and step-size bias: float32 whatever amp says
        self.A_log = self.create_parameter(
            (heads,), default_initializer=I.Uniform(0.0, math.log(16.0)))
        self.dt_bias = self.create_parameter(
            (heads,), default_initializer=I.Uniform(
                _inv_softplus(c.time_step_min),
                _inv_softplus(c.time_step_max)))
        # the gated norm's gain over a head's lanes (not zero-centred)
        self.norm_weight = self.create_parameter(
            (c.linear_value_head_dim,), default_initializer=I.Constant(1.0))
        for p in (self.A_log, self.dt_bias, self.norm_weight):
            p.keep_fp32 = True
        self.out_proj = Linear(
            self.value_dim, c.hidden_size,
            weight_attr=_normal(c.initializer_range
                                / math.sqrt(c.num_hidden_layers)),
            bias_attr=False)

    def forward(self, x):
        c = self.config
        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
        key_dim, value_dim = self.key_dim, self.value_dim
        eps, chunk = c.rms_norm_eps, c.chunk_size

        def raw(qkvz, ba, conv_w, a_log, dt_bias, norm_w):
            b, s, _ = qkvz.shape
            # convolution, SiLU, the split and q's and k's normalisation
            # over a head's lanes: flat (b, s, width) parts
            q, k, v = FS.conv_split_raw(
                qkvz, 0, ((key_dim, dk, 1.0 / math.sqrt(dk)),
                          (key_dim, dk, 1.0), (value_dim, None, 1.0)),
                conv_w, silu=True)
            q, k = q.reshape(b, s, hk, dk), k.reshape(b, s, hk, dk)
            v = v.reshape(b, s, hv, dv)
            z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, s, hv, dv)
            ba32 = ba.astype(jnp.float32)
            beta = jax.nn.sigmoid(ba32[..., :hv])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba32[..., hv:] + dt_bias)
            with _scopes.scope(_scopes.LINEAR_ATTN_SCAN):
                o = FL.gated_delta_rule_raw(q, k, v, g, beta, chunk)
            # the gated norm, over a head's lanes: float32, rounded once
            gated = (rms_norm_raw(o.astype(jnp.float32), norm_w, eps)
                     * jax.nn.silu(z.astype(jnp.float32)))
            return gated.astype(o.dtype).reshape(b, s, value_dim)

        y = call(raw, self.in_proj_qkvz(x), self.in_proj_ba(x),
                 self.conv1d_weight, self.A_log, self.dt_bias,
                 self.norm_weight, name="gated_delta_net")
        return self.out_proj(y)


class GatedAttention(Layer):
    """Causal grouped-query attention with per-head q/k norms, partial
    rotary and an output gate (the module's docstring).  ``q_proj``'s
    columns are ``[q | gate]`` a head."""

    _scope = _scopes.ATTN

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        c = config
        self.heads, self.kv_heads, self.head_dim = (
            c.num_attention_heads, c.num_key_value_heads, c.head_dim)
        self.rotary_dim = int(c.head_dim * c.partial_rotary_factor)
        self.theta = c.rope_theta
        attr = lambda: _normal(c.initializer_range)
        self.q_proj = Linear(c.hidden_size, 2 * self.heads * self.head_dim,
                             weight_attr=attr(), bias_attr=False)
        self.k_proj = Linear(c.hidden_size, self.kv_heads * self.head_dim,
                             weight_attr=attr(), bias_attr=False)
        self.v_proj = Linear(c.hidden_size, self.kv_heads * self.head_dim,
                             weight_attr=attr(), bias_attr=False)
        self.q_norm = RMSNorm(self.head_dim, c.rms_norm_eps,
                              zero_centered=True)
        self.k_norm = RMSNorm(self.head_dim, c.rms_norm_eps,
                              zero_centered=True)
        self.o_proj = Linear(
            self.heads * self.head_dim, c.hidden_size,
            weight_attr=_normal(c.initializer_range
                                / math.sqrt(c.num_hidden_layers)),
            bias_attr=False)

    def forward(self, x):
        b, s, _ = x.shape
        d, nq, nkv = self.head_dim, self.heads, self.kv_heads
        qg = ops.reshape(self.q_proj(x), [b, s, nq, 2 * d])
        q, gate = qg[:, :, :, :d], qg[:, :, :, d:]
        k = ops.reshape(self.k_proj(x), [b, s, nkv, d])
        v = ops.reshape(self.v_proj(x), [b, s, nkv, d])
        q = F.rotary_embedding(self.q_norm(q), self.rotary_dim, self.theta)
        k = F.rotary_embedding(self.k_norm(k), self.rotary_dim, self.theta)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        out = call(_gate_output, out, gate, name="attention_output_gate")
        return self.o_proj(ops.reshape(out, [b, s, nq * d]))


def _gate_output(out, gate):
    return (out.astype(jnp.float32)
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)


class Qwen3NextDecoderLayer(Layer):
    def __init__(self, config: Qwen3NextConfig, kind: str):
        super().__init__()
        c = config
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps,
                                       zero_centered=True)
        if kind == LINEAR:
            self.linear_attn = GatedDeltaNet(c)
        else:
            self.self_attn = GatedAttention(c)
        self.post_attention_layernorm = RMSNorm(
            c.hidden_size, c.rms_norm_eps, zero_centered=True)
        self.mlp = RoutedExperts(
            c.hidden_size, c.moe_intermediate_size, c.router_width,
            c.num_experts_per_tok, held=c.held_experts,
            shared_intermediate_size=c.shared_expert_intermediate_size,
            router="softmax", expert="gated", shared_gate=True)
        self.kind = kind

    def forward(self, x):
        mixer = self.linear_attn if self.kind == LINEAR else self.self_attn
        x = x + mixer(self.input_layernorm(x))
        return x + self.mlp(self.post_attention_layernorm(x))


class Qwen3NextModel(Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = config
        c = config
        self.embed_tokens = Embedding(
            c.vocab_size, c.hidden_size,
            weight_attr=_normal(c.initializer_range))
        self.layers = LayerList([Qwen3NextDecoderLayer(c, kind)
                                 for kind in c.layer_types])
        self.norm = RMSNorm(c.hidden_size, c.rms_norm_eps,
                            zero_centered=True)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        kinds = self.config.recompute if self.training else ()
        if kinds:
            from ..distributed.recompute import recompute
        for layer in self.layers:
            x = recompute(layer, x) if layer.kind in kinds else layer(x)
        return self.norm(x)


class Qwen3NextForCausalLM(Layer):
    """Token ids (b, s) -> logits (b, s, vocab); the head is untied."""

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = config
        self.model = Qwen3NextModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=_normal(config.initializer_range),
                              bias_attr=False)

    def forward(self, input_ids):
        x = self.model(input_ids)
        with _scopes.scope(_scopes.LM_HEAD):
            return self.lm_head(x)


def _normal(std):
    return ParamAttr(initializer=I.Normal(0.0, std))


def _inv_softplus(y: float) -> float:
    return math.log(math.expm1(y))
