"""Optimizer base (reference: python/paddle/optimizer/optimizer.py).

TPU-native split: every optimizer defines a *functional core*
(``init_one``/``update_one`` pure functions over jax arrays, the analogue of
the reference's per-param CUDA kernels in
paddle/fluid/operators/optimizers/), which serves two callers:

* the eager path — ``opt.step()`` reads ``param.grad`` tensors, runs one
  jitted fused update over the whole parameter list (XLA fuses the elementwise
  chains; the analogue of the reference's multi_tensor adam), writes arrays
  back in place;
* the compiled path — ``paddle_tpu.jit.TrainStep`` calls
  ``opt.apply_gradients(params_tree, grads_tree, state, lr)`` inside the
  traced step function.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..core.tensor import Parameter, Tensor
from .lr import LRScheduler


def mesh_of(p):
    """The multi-device mesh a concrete parameter array is laid out on, or
    None (one device, a tracer, a non-array leaf)."""
    sharding = getattr(p, "sharding", None)
    if isinstance(sharding, NamedSharding) and sharding.mesh.size > 1 \
            and not isinstance(p, jax.core.Tracer):
        return sharding.mesh
    return None


def _path_name(key_path) -> str:
    """Dotted leaf name from a jax key path for apply_decay_param_fun:
    DictKey exposes .key, GetAttrKey .name, SequenceKey .idx — str() of
    the entry itself would prepend separators ('.w', '[0]') and produce
    mangled names like 'layer1..w'."""
    parts = []
    for k in key_path:
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
        else:
            parts.append(str(k))
    return ".".join(parts)


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=None):
        if parameters is None:
            raise ValueError(
                "parameters=None: pass model.parameters() (static-graph "
                "implicit collection is not supported in the TPU build)")
        self._param_groups = self._build_groups(parameters)
        self._learning_rate = learning_rate
        self.regularization = weight_decay
        self._grad_clip = grad_clip
        self._wd = self._coeff(weight_decay)
        # regularizer.L1Decay objects flip the coupled term to wd*sign(p)
        self._wd_mode = getattr(weight_decay, "_mode", "l2")
        self._accumulators: Dict[int, dict] = {}
        self._step_count = 0
        self._jit_update = None
        self._name = name or type(self).__name__
        # fp32 master weights for low-precision params (reference:
        # optimizer.py _multi_precision + fluid/dygraph/amp/loss_scaler.py:40).
        # None = auto: on whenever a param is bf16/fp16 — without a master
        # copy, lr~1e-4 updates on O2 bf16 weights vanish below the bf16 ULP.
        self._multi_precision = multi_precision
        #: optimizers that support reduced-precision STATE set this (e.g.
        #: Adam(moment_dtype='bfloat16')); None = keep slots f32
        self._moment_dtype = None

    def _wants_master(self, p) -> bool:
        if self._multi_precision is False:
            return False
        return p.dtype in (jnp.bfloat16, jnp.float16)

    def _init_slots(self, p):
        slots = self.init_one(p)
        if self._wants_master(p):
            if self._moment_dtype is None:
                # all slots f32 from step 0: the master-path update returns
                # f32 slots, and a dtype flip between steps would silently
                # retrace the compiled train step and break buffer donation
                slots = {k: v.astype(jnp.float32)
                         if hasattr(v, "dtype") and jnp.issubdtype(
                             v.dtype, jnp.floating) else v
                         for k, v in slots.items()}
            # reduced-precision moments keep init_one's intentional dtypes
            slots["master"] = p.astype(jnp.float32)
        mesh = mesh_of(p)
        if mesh is not None:
            # state born on one device and returned mesh-placed by the
            # first step is a different jit key on the second (the aval
            # carries the mesh): a silent second compile.  Start every slot
            # where the step will leave it — laid out like its parameter
            slots = {k: jax.device_put(
                v, p.sharding if getattr(v, "shape", None) == p.shape
                else NamedSharding(mesh, PartitionSpec()))
                for k, v in slots.items()}
        return slots

    def _update_leaf(self, g, p, slots, lr, step, name=None):
        """update_one, routed through the fp32 master copy when present.

        ``name`` enables AdamW's ``apply_decay_param_fun`` (reference
        adamw.py:54): parameters the predicate rejects update with weight
        decay OFF.  The toggle is a host-side flip of self._wd around the
        (trace-time) update_one call, so each leaf bakes its own decay
        constant without widening the update_one subclass API; it assumes
        the standard single-threaded trace — concurrently tracing the
        SAME optimizer object from multiple threads could observe the
        flipped value."""
        fn = getattr(self, "_apply_decay_param_fun", None)
        if fn is not None and name is not None and self._wd \
                and not fn(name):
            saved = self._wd
            self._wd = 0.0
            try:
                return self._update_leaf(g, p, slots, lr, step)
            finally:
                self._wd = saved
        master = slots.get("master") if isinstance(slots, dict) else None
        if master is None:
            return self.update_one(g, p, slots, lr, step)
        inner = {k: v for k, v in slots.items() if k != "master"}
        new_master, new_inner = self.update_one(
            g.astype(jnp.float32), master, inner, lr, step)
        new_inner["master"] = new_master
        return new_master.astype(p.dtype), new_inner

    @staticmethod
    def _coeff(weight_decay):
        if weight_decay is None:
            return 0.0
        if isinstance(weight_decay, (int, float)):
            return float(weight_decay)
        # L2Decay-like object
        return float(getattr(weight_decay, "_coeff",
                             getattr(weight_decay, "coeff", 0.0)))

    def _build_groups(self, parameters):
        params = list(parameters)
        if params and isinstance(params[0], dict):
            return params
        return [{"params": params}]

    @property
    def _parameter_list(self) -> List[Parameter]:
        out = []
        for g in self._param_groups:
            out.extend(g["params"])
        return out

    # -- lr ------------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._learning_rate = float(value)

    # -- functional core (override in subclasses) ---------------------------
    def init_one(self, p):
        """Per-parameter slot init: array -> dict of arrays."""
        return {}

    def update_one(self, g, p, slots, lr, step):
        """Pure update: returns (new_p, new_slots)."""
        raise NotImplementedError

    # decoupled weight decay? (AdamW overrides)
    _decoupled_wd = False

    # -- compiled-path API ---------------------------------------------------
    def init_state(self, params_tree):
        step = jnp.zeros((), jnp.int32)
        meshes = [m for m in map(mesh_of,
                                 jax.tree_util.tree_leaves(params_tree))
                  if m is not None]
        if meshes:
            step = jax.device_put(step,
                                  NamedSharding(meshes[0], PartitionSpec()))
        return {
            "slots": jax.tree_util.tree_map(self._init_slots, params_tree),
            "step": step,
        }

    def apply_gradients(self, params_tree, grads_tree, state, lr):
        """Pure function for use inside jit: returns (new_params, new_state)."""
        step = state["step"] + 1
        p_leaves, treedef = jax.tree_util.tree_flatten(params_tree)
        # preserve None grads as leaves — bare tree_leaves would drop them
        # and misalign params with grads
        g_leaves = jax.tree_util.tree_flatten(
            grads_tree, is_leaf=lambda x: x is None)[0]
        # grad clip first (global norm across the whole tree)
        g_leaves = self._clip_tree(p_leaves, g_leaves)
        slot_leaves = _flatten_slots(state["slots"], treedef, len(p_leaves))
        names = [None] * len(p_leaves)
        if getattr(self, "_apply_decay_param_fun", None) is not None:
            # leaf names for the per-name decay filter — same traversal
            # order as tree_flatten
            paths = jax.tree_util.tree_flatten_with_path(params_tree)[0]
            names = [_path_name(kp) for kp, _ in paths]
        new_p, new_slots = [], []
        for p, g, s, nm in zip(p_leaves, g_leaves, slot_leaves, names):
            if g is None:
                new_p.append(p)
                new_slots.append(s)
                continue
            np_, ns = self._update_leaf(g, p, s, lr, step, name=nm)
            new_p.append(np_)
            new_slots.append(ns)
        params_out = jax.tree_util.tree_unflatten(treedef, new_p)
        slots_out = _unflatten_slots(new_slots, treedef)
        return params_out, {"slots": slots_out, "step": step}

    def _clip_tree(self, p_leaves, g_leaves, dist_flags=None):
        from ..nn import (ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue)
        clip = self._grad_clip
        if clip is None:
            return g_leaves
        live = [(i, g) for i, g in enumerate(g_leaves) if g is not None]
        if isinstance(clip, ClipGradByGlobalNorm):
            if hasattr(clip, "_total_norm"):
                # mp-aware subclass (fleet.HybridParallelOptimizer): norms of
                # distributed params are psum'd over the model-parallel axis
                total = clip._total_norm(live, dist_flags)
            else:
                total = jnp.sqrt(
                    sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for _, g in live))
            coef = clip.clip_norm / jnp.maximum(total, clip.clip_norm)
            out = list(g_leaves)
            for i, g in live:
                out[i] = (g.astype(jnp.float32) * coef).astype(g.dtype)
            return out
        if isinstance(clip, ClipGradByNorm):
            out = list(g_leaves)
            for i, g in live:
                n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
                coef = clip.clip_norm / jnp.maximum(n, clip.clip_norm)
                out[i] = (g.astype(jnp.float32) * coef).astype(g.dtype)
            return out
        if isinstance(clip, ClipGradByValue):
            out = list(g_leaves)
            for i, g in live:
                out[i] = jnp.clip(g, clip.min, clip.max)
            return out
        return g_leaves

    # -- eager path ----------------------------------------------------------
    def step(self):
        params = [p for p in self._parameter_list
                  if (not p.stop_gradient) and p.grad is not None]
        if not params:
            self._step_count += 1
            self._post_step()
            return
        key = tuple(id(p) for p in params)
        if self._jit_update is None or self._jit_key != key:
            self._jit_key = key
            for p in params:
                if id(p) not in self._accumulators:
                    self._accumulators[id(p)] = self._init_slots(p._array)

            flags = [bool(getattr(p, "is_distributed", False))
                     for p in params]

            # host-side constants for the per-name decay filter
            # (apply_decay_param_fun); baked into the jitted update
            names = [getattr(p, "name", None) for p in params]

            def _update(p_arrs, g_arrs, slot_list, lr, step):
                g_arrs = self._clip_tree(p_arrs, list(g_arrs),
                                         dist_flags=flags)
                new_p, new_s = [], []
                for p, g, s, nm in zip(p_arrs, g_arrs, slot_list, names):
                    np_, ns = self._update_leaf(g, p, s, lr, step,
                                                name=nm)
                    new_p.append(np_)
                    new_s.append(ns)
                return new_p, new_s

            self._jit_update = jax.jit(_update)
        slot_list = [self._accumulators[id(p)] for p in params]
        self._step_count += 1
        lr = jnp.asarray(self.get_lr(), jnp.float32)
        step = jnp.asarray(self._step_count, jnp.int32)
        new_p, new_s = self._jit_update(
            [p._array for p in params],
            [p.grad._array.astype(p._array.dtype) for p in params],
            slot_list, lr, step)
        for p, arr, s in zip(params, new_p, new_s):
            p._array = arr
            self._accumulators[id(p)] = s
        self._post_step()

    def _post_step(self):
        pass

    def clear_grad(self, set_to_zero=False):
        for p in self._parameter_list:
            p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        loss.backward()
        self.step()
        self.clear_grad()
        return None, None

    # -- state dict ----------------------------------------------------------
    def state_dict(self):
        sd = {"_step_count": self._step_count}
        for i, p in enumerate(self._parameter_list):
            slots = self._accumulators.get(id(p))
            if slots:
                for k, v in slots.items():
                    sd[f"{p.name or i}@{k}"] = Tensor(v)
        if isinstance(self._learning_rate, LRScheduler):
            sd["LR_Scheduler"] = self._learning_rate.state_dict()
        return sd

    def set_state_dict(self, sd):
        self._step_count = int(sd.get("_step_count", 0))
        if isinstance(self._learning_rate, LRScheduler) and "LR_Scheduler" in sd:
            self._learning_rate.set_state_dict(sd["LR_Scheduler"])
        for i, p in enumerate(self._parameter_list):
            slots = {}
            prefix = f"{p.name or i}@"
            for k, v in sd.items():
                if isinstance(k, str) and k.startswith(prefix):
                    arr = v._array if isinstance(v, Tensor) else jnp.asarray(v)
                    slots[k[len(prefix):]] = arr
            if slots:
                self._accumulators[id(p)] = slots
                self._jit_update = None  # force refresh

    set_dict = set_state_dict


def _flatten_slots(slots_tree, treedef, n):
    """slots_tree mirrors params_tree but with dict-of-arrays leaves."""
    return jax.tree_util.tree_flatten(
        slots_tree, is_leaf=lambda x: isinstance(x, dict) and
        all(not isinstance(v, dict) for v in x.values()))[0][:n]


def _unflatten_slots(slot_leaves, treedef):
    return jax.tree_util.tree_unflatten(treedef, slot_leaves)
