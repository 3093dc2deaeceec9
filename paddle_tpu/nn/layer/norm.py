"""Norm layers (reference: python/paddle/nn/layer/norm.py)."""
from __future__ import annotations

import numpy as np

from ...core.tensor import Tensor
from .. import functional as F
from .. import initializer as I
from ...observability import scopes as _scopes
from .layers import Layer


class LayerNorm(Layer):
    _scope = _scopes.NORM

    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self.normalized_shape = list(normalized_shape)
        self.epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                self.normalized_shape, attr=weight_attr,
                default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                self.normalized_shape, attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.layer_norm(x, self.normalized_shape, self.weight, self.bias,
                            self.epsilon)

    def extra_repr(self):
        return f"normalized_shape={self.normalized_shape}, epsilon={self.epsilon}"


class RMSNorm(Layer):
    _scope = _scopes.NORM

    def __init__(self, hidden_size, epsilon=1e-6, zero_centered=False):
        super().__init__()
        self.epsilon = epsilon
        # a zero-centred gain is stored as its distance from one and
        # applied as ``1 + weight`` (Qwen3-Next's norms)
        self.zero_centered = zero_centered
        self.weight = self.create_parameter(
            (hidden_size,), default_initializer=I.Constant(
                0.0 if zero_centered else 1.0))
        self.weight.keep_fp32 = True   # as LayerNorm's under amp O2

    def forward(self, x):
        if self.zero_centered:
            return F.rms_norm(x, self.weight, self.epsilon, True)
        return F.rms_norm(x, self.weight, self.epsilon)


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self.num_features = num_features
        self.momentum = momentum
        self.epsilon = epsilon
        self.data_format = data_format
        self.use_global_stats = use_global_stats
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                (num_features,), attr=weight_attr,
                default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                (num_features,), attr=bias_attr, is_bias=True)
        self.register_buffer("_mean", Tensor(np.zeros(num_features, np.float32)))
        self.register_buffer("_variance", Tensor(np.ones(num_features, np.float32)))

    def forward(self, x):
        return F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self.momentum,
            epsilon=self.epsilon, data_format=self.data_format,
            use_global_stats=self.use_global_stats)

    def extra_repr(self):
        return f"num_features={self.num_features}, momentum={self.momentum}"


class BatchNorm(_BatchNormBase):
    pass


class BatchNorm1D(_BatchNormBase):
    pass


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCDHW",
                 use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, data_format, use_global_stats, name)


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica batch norm.

    Under pjit/GSPMD the batch axis is sharded and XLA computes global batch
    statistics automatically when the reduction spans the data axis — so
    SyncBatchNorm is behaviourally BatchNorm here (reference needed a custom
    NCCL kernel: paddle/fluid/operators/sync_batch_norm_op.cu).
    """

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        for _, sub in layer.named_sublayers(include_self=True):
            pass
        return layer


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self.num_groups = num_groups
        self.num_channels = num_channels
        self.epsilon = epsilon
        self.data_format = data_format
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                (num_channels,), attr=weight_attr,
                default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                (num_channels,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.group_norm(x, self.num_groups, self.weight, self.bias,
                            self.epsilon, self.data_format)


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self.epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                (num_features,), attr=weight_attr,
                default_initializer=I.Constant(1.0))
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(
                (num_features,), attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, weight=self.weight, bias=self.bias,
                               eps=self.epsilon)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW"):
        super().__init__()
        self.size = size
        self.alpha = alpha
        self.beta = beta
        self.k = k
        self.data_format = data_format

    def forward(self, x):
        return F.local_response_norm(x, self.size, self.alpha, self.beta,
                                     self.k, self.data_format)


class SpectralNorm(Layer):
    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12):
        super().__init__()
        self.dim = dim
        self.power_iters = power_iters
        self.eps = eps

    def forward(self, weight):
        return F.spectral_norm(weight, self.power_iters, self.eps, self.dim)
