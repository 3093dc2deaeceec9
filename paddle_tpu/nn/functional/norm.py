"""Normalization functionals (reference: python/paddle/nn/functional/norm.py).

layer_norm is plain jnp: XLA fuses the f32 statistics into the neighbouring
ops (PERF.md section 6).  batch_norm keeps running stats on the layer like
the reference (paddle/phi/kernels/gpu/batch_norm_kernel.cu semantics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import call, wrap_op
from ...core.tensor import Tensor


def layer_norm_raw(x, weight, bias, normalized_shape, epsilon=1e-5):
    n_axes = len(normalized_shape) if isinstance(normalized_shape, (list, tuple)) else 1
    axes = tuple(range(x.ndim - n_axes, x.ndim))
    # statistics in f32 regardless of activation dtype, output cast back to
    # the input dtype: keeps bf16 activations bf16 through the residual
    # stream (an f32-promoting LN silently turns every downstream matmul
    # into an f32 MXU op — measured 0.42x -> the dominant bench regression)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        out = out * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


@wrap_op
def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5):
    return layer_norm_raw(x, weight, bias, normalized_shape, epsilon)


def rms_norm_raw(x, weight, epsilon=1e-6, zero_centered=False):
    """``x / sqrt(mean x^2 + epsilon) * weight`` in float32; a zero-centred
    gain multiplies by ``1 + weight`` (the gain is stored as its distance
    from one, so weight decay pulls it to one)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + epsilon)
    if weight is not None:
        gain = weight.astype(jnp.float32)
        out = out * (1.0 + gain if zero_centered else gain)
    return out.astype(x.dtype)


@wrap_op
def rms_norm(x, weight=None, epsilon=1e-6, zero_centered=False):
    return rms_norm_raw(x, weight, epsilon, zero_centered)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None):
    """Batch norm with running-stat update on the provided mean/var tensors."""
    if use_global_stats is None:
        use_global_stats = not training
    ch_axis = 1 if data_format.startswith("NC") else x.ndim - 1 if isinstance(x, Tensor) else 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)

    if use_global_stats:
        def raw(a, rm, rv, w, b):
            shape = [1] * a.ndim
            shape[ch_axis] = -1
            out = (a - rm.reshape(shape)) * jax.lax.rsqrt(rv.reshape(shape) + epsilon)
            if w is not None:
                out = out * w.reshape(shape)
            if b is not None:
                out = out + b.reshape(shape)
            return out
        return call(raw, x, running_mean.detach(), running_var.detach(),
                    weight, bias, name="batch_norm_infer")

    # training: compute batch stats; update running stats eagerly (or, under
    # trace, via the functional-state mechanism in jit.functional_call)
    def raw(a, w, b):
        mean = jnp.mean(a, axis=reduce_axes)
        var = jnp.var(a, axis=reduce_axes)
        shape = [1] * a.ndim
        shape[ch_axis] = -1
        out = (a - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + epsilon)
        if w is not None:
            out = out * w.reshape(shape)
        if b is not None:
            out = out + b.reshape(shape)
        return out, mean, var

    out, batch_mean, batch_var = call(raw, x, weight, bias, name="batch_norm")
    # running-stat update (mirrors reference momentum semantics:
    # running = momentum*running + (1-momentum)*batch)
    if running_mean is not None:
        running_mean._array = (momentum * running_mean._array
                               + (1.0 - momentum) * batch_mean._array.astype(running_mean._array.dtype))
    if running_var is not None:
        n = 1
        for i in reduce_axes:
            n *= x.shape[i]
        unbiased = batch_var._array * (n / max(n - 1, 1))
        running_var._array = (momentum * running_var._array
                              + (1.0 - momentum) * unbiased.astype(running_var._array.dtype))
    return out


@wrap_op
def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW"):
    if data_format.startswith("NC"):
        n, c = x.shape[0], x.shape[1]
        spatial = x.shape[2:]
        g = x.reshape((n, num_groups, c // num_groups) + spatial)
        axes = tuple(range(2, g.ndim))
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        g = (g - mean) * jax.lax.rsqrt(var + epsilon)
        out = g.reshape(x.shape)
        shape = (1, c) + (1,) * len(spatial)
        if weight is not None:
            out = out * weight.reshape(shape)
        if bias is not None:
            out = out + bias.reshape(shape)
        return out
    raise NotImplementedError("group_norm NHWC")


@wrap_op
def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW"):
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        shape = (1, -1) + (1,) * (x.ndim - 2)
        out = out * weight.reshape(shape)
    if bias is not None:
        shape = (1, -1) + (1,) * (x.ndim - 2)
        out = out + bias.reshape(shape)
    return out


@wrap_op
def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW"):
    ch_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    sq = jnp.square(x)
    half = size // 2
    pad_cfg = [(0, 0)] * x.ndim
    pad_cfg[ch_axis] = (half, size - half - 1)
    padded = jnp.pad(sq, pad_cfg)
    windows = sum(jnp.take(padded, jnp.arange(i, i + x.shape[ch_axis]),
                           axis=ch_axis) for i in range(size))
    denom = (k + alpha * windows / size) ** beta
    return x / denom


def spectral_norm(weight, n_power_iterations=1, eps=1e-12, dim=0):
    def raw(w):
        wm = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
        u = jnp.ones((wm.shape[0],), w.dtype)
        v = jnp.ones((wm.shape[1],), w.dtype)
        for _ in range(max(n_power_iterations, 1)):
            v = wm.T @ u
            v = v / jnp.maximum(jnp.linalg.norm(v), eps)
            u = wm @ v
            u = u / jnp.maximum(jnp.linalg.norm(u), eps)
        sigma = u @ wm @ v
        return w / sigma
    return call(raw, weight, name="spectral_norm")
