"""Concrete optimizers (reference: python/paddle/optimizer/{sgd,momentum,adam,
adamw,adagrad,adadelta,rmsprop,adamax,lamb}.py; CUDA kernels they wrapped:
paddle/fluid/operators/optimizers/).

Each defines only the pure per-parameter update; fusion across the parameter
list is done by XLA in the jitted update (see optimizer.py).
"""
from __future__ import annotations

import jax.numpy as jnp

from .optimizer import Optimizer


def _wd_grad(self, g, p):
    """Coupled weight decay: g + wd * p (L2Decay / float) or
    g + wd * sign(p) (regularizer.L1Decay)."""
    if self._wd and not self._decoupled_wd:
        if getattr(self, "_wd_mode", "l2") == "l1":
            return g + jnp.asarray(self._wd, g.dtype) * jnp.sign(p)
        return g + jnp.asarray(self._wd, g.dtype) * p
    return g


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)

    def update_one(self, g, p, slots, lr, step):
        g = _wd_grad(self, g, p)
        return p - lr.astype(p.dtype) * g, slots


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 name=None, multi_precision=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def init_one(self, p):
        return {"velocity": jnp.zeros_like(p)}

    def update_one(self, g, p, slots, lr, step):
        g = _wd_grad(self, g, p)
        mu = jnp.asarray(self._momentum, p.dtype)
        v = mu * slots["velocity"] + g
        if self._nesterov:
            upd = g + mu * v
        else:
            upd = v
        return p - lr.astype(p.dtype) * upd, {"velocity": v}


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, name=None,
                 multi_precision=None, amsgrad=False, moment_dtype=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._amsgrad = amsgrad
        # opt-in reduced-precision optimizer state: moments stored in e.g.
        # bf16 (the update math stays f32).  Cuts the AdamW step's HBM
        # traffic from 28 to 20 B/param — the update bucket is bandwidth-
        # bound at 3x its floor (PERF.md).  Default None keeps exact f32
        # state (reference semantics).
        if moment_dtype is not None:
            from ..core.dtype import convert_dtype
            self._moment_dtype = jnp.dtype(convert_dtype(moment_dtype))

    def _mdt(self):
        return self._moment_dtype or jnp.float32

    def init_one(self, p):
        mdt = self._mdt()
        slots = {"moment1": jnp.zeros(p.shape, mdt),
                 "moment2": jnp.zeros(p.shape, mdt)}
        if self._amsgrad:
            slots["moment2_max"] = jnp.zeros(p.shape, mdt)
        return slots

    # NOTE: a fused Pallas AdamW kernel was tried for the mid-size-param
    # update inefficiency (XLA's per-param fusions run ~250 GB/s vs ~700 on
    # big arrays, PERF.md) and measured SLOWER end-to-end on the 345M bench
    # (45.4k vs 52.2k tokens/s — per-pallas_call overhead x ~150 params
    # dominates); the XLA fusion path below stays.

    def update_one(self, g, p, slots, lr, step):
        g = _wd_grad(self, g, p)
        g32 = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        mdt = self._mdt()
        b1 = self._beta1
        b2 = self._beta2
        # math in f32 regardless of the STORAGE dtype of the moments
        m = b1 * slots["moment1"].astype(jnp.float32) + (1 - b1) * g32
        v = b2 * slots["moment2"].astype(jnp.float32) \
            + (1 - b2) * jnp.square(g32)
        t = step.astype(jnp.float32)
        mhat = m / (1 - b1 ** t)
        if self._amsgrad:
            vmax = jnp.maximum(slots["moment2_max"].astype(jnp.float32), v)
            vhat = vmax / (1 - b2 ** t)
            new_slots = {"moment1": m.astype(mdt), "moment2": v.astype(mdt),
                         "moment2_max": vmax.astype(mdt)}
        else:
            vhat = v / (1 - b2 ** t)
            new_slots = {"moment1": m.astype(mdt),
                         "moment2": v.astype(mdt)}
        if self._decoupled_wd and self._wd:
            p32 = p32 * (1.0 - lr * self._wd)
        new_p = p32 - lr * mhat / (jnp.sqrt(vhat) + self._epsilon)
        return new_p.astype(p.dtype), new_slots


class AdamW(Adam):
    _decoupled_wd = True

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=None, name=None,
                 amsgrad=False, moment_dtype=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, name,
                         multi_precision, amsgrad, moment_dtype)
        self._apply_decay_param_fun = apply_decay_param_fun
        if self._wd_mode == "l1":
            # AdamW's decoupled update p *= (1 - lr*wd) is L2-SHAPED — an
            # L1Decay coefficient used to be silently applied as L2.  L1
            # has no decoupled analogue here, so route it through the
            # coupled wd*sign(p) gradient term instead (instance override
            # of the class-level _decoupled_wd; _wd_grad then applies it).
            self._decoupled_wd = False


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 initial_accumulator_value=0.0):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value

    def init_one(self, p):
        return {"moment": jnp.full(p.shape, self._init_acc, jnp.float32)}

    def update_one(self, g, p, slots, lr, step):
        g = _wd_grad(self, g, p).astype(jnp.float32)
        acc = slots["moment"] + jnp.square(g)
        new_p = p.astype(jnp.float32) - lr * g / (jnp.sqrt(acc) + self._epsilon)
        return new_p.astype(p.dtype), {"moment": acc}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._epsilon = epsilon
        self._rho = rho

    def init_one(self, p):
        return {"avg_squared_grad": jnp.zeros(p.shape, jnp.float32),
                "avg_squared_update": jnp.zeros(p.shape, jnp.float32)}

    def update_one(self, g, p, slots, lr, step):
        g = _wd_grad(self, g, p).astype(jnp.float32)
        rho, eps = self._rho, self._epsilon
        asg = rho * slots["avg_squared_grad"] + (1 - rho) * jnp.square(g)
        upd = g * jnp.sqrt(slots["avg_squared_update"] + eps) / jnp.sqrt(asg + eps)
        asu = rho * slots["avg_squared_update"] + (1 - rho) * jnp.square(upd)
        new_p = p.astype(jnp.float32) - lr * upd
        return new_p.astype(p.dtype), {"avg_squared_grad": asg,
                                       "avg_squared_update": asu}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered

    def init_one(self, p):
        s = {"mean_square": jnp.zeros(p.shape, jnp.float32),
             "momentum": jnp.zeros(p.shape, jnp.float32)}
        if self._centered:
            s["mean_grad"] = jnp.zeros(p.shape, jnp.float32)
        return s

    def update_one(self, g, p, slots, lr, step):
        g = _wd_grad(self, g, p).astype(jnp.float32)
        rho, eps = self._rho, self._epsilon
        ms = rho * slots["mean_square"] + (1 - rho) * jnp.square(g)
        if self._centered:
            mg = rho * slots["mean_grad"] + (1 - rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + eps)
            new_slots = {"mean_square": ms, "mean_grad": mg}
        else:
            denom = jnp.sqrt(ms + eps)
            new_slots = {"mean_square": ms}
        mom = self._momentum * slots["momentum"] + lr * g / denom
        new_slots["momentum"] = mom
        new_p = p.astype(jnp.float32) - mom
        return new_p.astype(p.dtype), new_slots


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def init_one(self, p):
        return {"moment": jnp.zeros(p.shape, jnp.float32),
                "inf_norm": jnp.zeros(p.shape, jnp.float32)}

    def update_one(self, g, p, slots, lr, step):
        g = _wd_grad(self, g, p).astype(jnp.float32)
        b1, b2 = self._beta1, self._beta2
        m = b1 * slots["moment"] + (1 - b1) * g
        u = jnp.maximum(b2 * slots["inf_norm"], jnp.abs(g))
        t = step.astype(jnp.float32)
        new_p = (p.astype(jnp.float32)
                 - (lr / (1 - b1 ** t)) * m / (u + self._epsilon))
        return new_p.astype(p.dtype), {"moment": m, "inf_norm": u}


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def init_one(self, p):
        return {"moment1": jnp.zeros(p.shape, jnp.float32),
                "moment2": jnp.zeros(p.shape, jnp.float32)}

    def update_one(self, g, p, slots, lr, step):
        g32 = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        b1, b2 = self._beta1, self._beta2
        m = b1 * slots["moment1"] + (1 - b1) * g32
        v = b2 * slots["moment2"] + (1 - b2) * jnp.square(g32)
        t = step.astype(jnp.float32)
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        r = mhat / (jnp.sqrt(vhat) + self._epsilon) + self._lamb_wd * p32
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p32)))
        r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        new_p = p32 - lr * trust * r
        return new_p.astype(p.dtype), {"moment1": m, "moment2": v}
