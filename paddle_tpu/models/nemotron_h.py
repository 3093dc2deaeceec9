"""Nemotron-H — hybrid decoder of Mamba-2, routed-expert and attention
blocks (NVIDIA Nemotron 3 Nano's family, ``model_type: nemotron_h``).

Every block is one mixer alone, ``x <- x + mixer(RMSNorm(x))``, its kind
chosen by a character of ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer
(``nn.functional.ssm``), ``E`` a routed expert layer that is told which
experts it holds (``nn.layer.experts``), ``*`` grouped-query attention
through the flash kernels.  Then a final RMSNorm and an untied head.  No
position embedding anywhere: the family takes its attention from Jamba's
and applies no rotary.

Import it from here; ``paddle_tpu.models`` does not (a process that trains
GPT-2 pays nothing for it).
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
from ..nn.functional import ssm as FS
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.experts import RoutedExperts
from ..nn.layer.layers import Layer, LayerList
from ..nn.layer.norm import RMSNorm
from ..observability import scopes as _scopes
from .gpt import GPTPretrainingCriterion as NemotronHPretrainingCriterion

__all__ = ["NemotronHConfig", "NemotronHForCausalLM",
           "NemotronHPretrainingCriterion"]

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


@dataclasses.dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = "MEMEM*EME"
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # experts: ``n_routed_experts`` are held here, ``held_experts`` says
    # which of the router's ``router_width`` (default: all, in order)
    n_routed_experts: int = 128
    router_width: Optional[int] = None
    held_experts: Optional[Tuple[int, ...]] = None
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    # the kinds of block that are a jax.checkpoint in training ("M", "ME",
    # "ME*"; "" none): a row of 8k tokens keeps ~1 GB of activations a block
    # otherwise
    recompute: str = ""

    def __post_init__(self):
        if self.router_width is None:
            self.router_width = self.n_routed_experts
        if self.held_experts is None:
            self.held_experts = tuple(range(self.n_routed_experts))
        self.held_experts = tuple(self.held_experts)
        if len(self.held_experts) != self.n_routed_experts:
            raise ValueError("held_experts %r does not list "
                             "n_routed_experts = %d ids"
                             % (self.held_experts, self.n_routed_experts))
        unknown = set(self.hybrid_override_pattern + self.recompute) - {
            MAMBA, EXPERTS, ATTENTION}
        if unknown:
            raise ValueError("hybrid_override_pattern / recompute: unknown "
                             "block kinds %r" % sorted(unknown))

    @property
    def num_hidden_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    @classmethod
    def tiny(cls, **kw):  # for tests
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, hybrid_override_pattern="ME*",
            mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
            n_groups=2, chunk_size=16, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, n_routed_experts=8,
            num_experts_per_tok=2, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=48), **kw})


class Mamba2Mixer(Layer):
    """``[z | xBC | dt] = in_proj(u)``; ``xBC = silu(conv(xBC))``; the
    scan over heads of ``mamba_head_dim`` with ``n_groups`` shared B and C;
    ``out_proj(GroupRMSNorm(y * silu(z)))``."""

    _scope = _scopes.SSM

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c = config
        self.config = c
        self.d_inner = c.mamba_num_heads * c.mamba_head_dim
        self.conv_dim = self.d_inner + 2 * c.n_groups * c.ssm_state_size
        heads = c.mamba_num_heads
        self.in_proj = Linear(
            c.hidden_size, self.d_inner + self.conv_dim + heads,
            weight_attr=_normal(c.initializer_range), bias_attr=False)
        # taps first: tap j multiplies x_{t-(k-1)+j}
        self.conv1d_weight = self.create_parameter(
            (c.conv_kernel, self.conv_dim),
            default_initializer=I.Uniform(-0.5, 0.5))
        self.conv1d_bias = self.create_parameter((self.conv_dim,),
                                                 is_bias=True)
        # decay rate, step-size bias and skip: float32 whatever amp says
        self.A_log = self.create_parameter(
            (heads,), default_initializer=I.Uniform(0.0, math.log(16.0)))
        self.dt_bias = self.create_parameter(
            (heads,), default_initializer=I.Uniform(
                _inv_softplus(c.time_step_min),
                _inv_softplus(c.time_step_max)))
        self.D = self.create_parameter((heads,),
                                       default_initializer=I.Constant(1.0))
        for p in (self.A_log, self.dt_bias, self.D):
            p.keep_fp32 = True
        self.norm_weight = self.create_parameter(
            (self.d_inner,), default_initializer=I.Constant(1.0))
        self.norm_weight.keep_fp32 = True
        self.out_proj = Linear(
            self.d_inner, c.hidden_size,
            weight_attr=_normal(c.initializer_range
                                / math.sqrt(c.num_hidden_layers)),
            bias_attr=False)

    def forward(self, u):
        c = self.config
        heads, p = c.mamba_num_heads, c.mamba_head_dim
        g, n, d_inner = c.n_groups, c.ssm_state_size, self.d_inner
        conv_dim, eps, chunk = self.conv_dim, c.layer_norm_epsilon, \
            c.chunk_size

        def raw(proj, conv_w, conv_b, a_log, dt_bias, d, norm_w):
            b, s, _ = proj.shape
            z = proj[..., :d_inner]
            dt = proj[..., d_inner + conv_dim:]
            # convolution, SiLU and the split: flat (b, s, width) parts
            x, bmat, cmat = FS.conv_split_raw(
                proj, d_inner, ((d_inner, None, 1.0), (g * n, None, 1.0),
                                (g * n, None, 1.0)),
                conv_w, conv_b, silu=True)
            x = x.reshape(b, s, heads, p)
            bmat, cmat = bmat.reshape(b, s, g, n), cmat.reshape(b, s, g, n)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            with _scopes.scope(_scopes.SSM_SCAN):
                y = FS.ssd_scan_raw(x, dt, -jnp.exp(a_log), bmat, cmat, d,
                                    chunk)
            return FS.gated_group_rms_norm_raw(
                y.reshape(b, s, d_inner), z, norm_w, g, eps)

        y = call(raw, self.in_proj(u), self.conv1d_weight, self.conv1d_bias,
                 self.A_log, self.dt_bias, self.D, self.norm_weight,
                 name="mamba2_mixer")
        return self.out_proj(y)


class GroupedQueryAttention(Layer):
    """Causal attention, ``num_attention_heads`` query heads over
    ``num_key_value_heads`` key/value heads (query head h reads key/value
    head h // (heads / kv heads)); no bias, no positions."""

    _scope = _scopes.ATTN

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        c = config
        self.heads, self.kv_heads, self.head_dim = (
            c.num_attention_heads, c.num_key_value_heads, c.head_dim)
        # one projection, columns [q | k | v]
        self.qkv_proj = Linear(
            c.hidden_size, (self.heads + 2 * self.kv_heads) * self.head_dim,
            weight_attr=_normal(c.initializer_range), bias_attr=False)
        self.o_proj = Linear(
            self.heads * self.head_dim, c.hidden_size,
            weight_attr=_normal(c.initializer_range
                                / math.sqrt(c.num_hidden_layers)),
            bias_attr=False)

    def forward(self, x):
        b, s, _ = x.shape
        d, nq, nkv = self.head_dim, self.heads, self.kv_heads
        qkv = self.qkv_proj(x)
        q = ops.reshape(qkv[:, :, :nq * d], [b, s, nq, d])
        k = ops.reshape(qkv[:, :, nq * d:(nq + nkv) * d], [b, s, nkv, d])
        v = ops.reshape(qkv[:, :, (nq + nkv) * d:], [b, s, nkv, d])
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        return self.o_proj(ops.reshape(out, [b, s, nq * d]))


class NemotronHBlock(Layer):
    def __init__(self, config: NemotronHConfig, kind: str):
        super().__init__()
        c = config
        self.norm = RMSNorm(c.hidden_size, c.layer_norm_epsilon)
        if kind == MAMBA:
            self.mixer = Mamba2Mixer(c)
        elif kind == ATTENTION:
            self.mixer = GroupedQueryAttention(c)
        else:
            self.mixer = RoutedExperts(
                c.hidden_size, c.moe_intermediate_size, c.router_width,
                c.num_experts_per_tok, held=c.held_experts,
                shared_intermediate_size=c
                .moe_shared_expert_intermediate_size,
                routed_scaling_factor=c.routed_scaling_factor)

    def forward(self, x):
        return x + self.mixer(self.norm(x))


class NemotronHModel(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        c = config
        self.embeddings = Embedding(c.vocab_size, c.hidden_size,
                                    weight_attr=_normal(c.initializer_range))
        self.layers = LayerList([NemotronHBlock(c, kind)
                                 for kind in c.hybrid_override_pattern])
        self.norm_f = RMSNorm(c.hidden_size, c.layer_norm_epsilon)

    def forward(self, input_ids):
        x = self.embeddings(input_ids)
        kinds = self.config.recompute if self.training else ""
        if kinds:
            from ..distributed.recompute import recompute
        for block, kind in zip(self.layers,
                               self.config.hybrid_override_pattern):
            x = recompute(block, x) if kind in kinds else block(x)
        return self.norm_f(x)


class NemotronHForCausalLM(Layer):
    """Token ids (b, s) -> logits (b, s, vocab); the head is untied."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.backbone = NemotronHModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=_normal(config.initializer_range),
                              bias_attr=False)

    def forward(self, input_ids):
        x = self.backbone(input_ids)
        with _scopes.scope(_scopes.LM_HEAD):
            return self.lm_head(x)


def _normal(std):
    return ParamAttr(initializer=I.Normal(0.0, std))


def _inv_softplus(y: float) -> float:
    return math.log(math.expm1(y))
