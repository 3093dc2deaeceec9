"""paddle_tpu.jit — the compiled training/inference path.

The analogue of the reference's dy2static + executors
(python/paddle/jit/to_static, fluid/executor.py, new_executor/InterpreterCore):
instead of AST transformation to a ProgramDesc interpreted by a C++ runtime,
a Layer's forward is *traced through jax.jit* into one XLA executable.

Three pieces:
* ``functional_call(layer, state, *args)`` — run a Layer against an external
  {name: array} state pytree (params + buffers), returning outputs plus the
  updated buffer state (running BN stats etc.).
* ``to_static(layer_or_fn)`` — paddle.jit.to_static equivalent; returns a
  compiled callable with the same signature.
* ``TrainStep`` — the Executor analogue: one jitted (and optionally pjit-
  sharded) function computing loss, grads and optimizer update.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..core import random as _rnd
from ..core.grad_mode import no_grad
from ..core.tensor import Tensor
from ..nn.layer.layers import Layer
from ..observability import liveness as _liveness
from ..observability import scopes as _scopes
from ..observability import tracing as _tracing
from ..robustness.faultpoints import declare as _declare, faultpoint

_declare("train.grads",
         "mutate the host-side batch before the compiled step (NaNBatch "
         "here yields NaN loss + NaN grads at a chosen step)")

# liveness beacon over one compiled TrainStep call (dispatch + the
# opt-in grad-norm sync); 600s default covers the first call's XLA
# compile — a wedged collective inside the step stalls it
_liveness.declare_beacon(
    "train.step", "one compiled TrainStep call (forward + backward + "
    "optimizer dispatch)", deadline=600.0)

__all__ = ["functional_call", "to_static", "TrainStep", "not_to_static",
           "save", "load", "TranslatedLayer"]


def _unwrap(x):
    return x._array if isinstance(x, Tensor) else x


def _unwrap_tree(tree):
    return jax.tree_util.tree_map(
        _unwrap, tree, is_leaf=lambda l: isinstance(l, Tensor))


def _wrap_tree(tree):
    return jax.tree_util.tree_map(lambda l: Tensor(l) if hasattr(l, "dtype") else l, tree)


def functional_call(layer: Layer, state: Dict[str, Any], *args,
                    rng=None, **kwargs):
    """Run ``layer`` with parameters/buffers taken from ``state``.

    Returns ``(outputs, new_state)`` where new_state reflects any buffer
    mutation during forward (e.g. batch-norm running stats).  Pure w.r.t.
    (state, args, rng) — safe to trace under jit/grad.
    """
    sd = layer.state_dict()
    old = {k: t._array for k, t in sd.items()}
    try:
        for k, arr in state.items():
            if k in sd:
                sd[k]._array = arr
        ctx = _rnd.key_stream(rng) if rng is not None else _nullcontext()
        with no_grad(), ctx:
            out = layer(*args, **kwargs)
        new_state = {k: sd[k]._array for k in state.keys() if k in sd}
        out_arrays = _unwrap_tree(out)
        return out_arrays, new_state
    finally:
        for k, arr in old.items():
            sd[k]._array = arr


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


class StaticFunction:
    """Compiled wrapper around a Layer or function
    (reference: program_translator.py:236 StaticFunction).

    Before jitting, the target's source is run through the dy2static AST
    pass (jit/dy2static.py — reference ast_transformer.py) so data-dependent
    Python ``if``/``while`` lower to lax.cond/while_loop instead of raising
    a tracer error.  Unsupported control-flow shapes fall back to trace-only
    compilation; the reason is kept on ``_dy2static_error``."""

    def __init__(self, target, input_spec=None, build_strategy=None,
                 backend=None):
        from .dy2static import Dy2StaticUnsupportedError, transform_function

        self._target = target
        self._input_spec = input_spec
        self._is_layer = isinstance(target, Layer)
        self._dy2static_error = None
        self._forward_override = None   # transformed forward, NOT written
        try:                            # onto the user's eager layer
            if self._is_layer:
                tf = transform_function(type(target).forward)
                if getattr(tf, "__dy2static_transformed__", False):
                    self._forward_override = tf
            else:
                tf = transform_function(target)
                if getattr(tf, "__dy2static_transformed__", False):
                    self._target = tf
        except Dy2StaticUnsupportedError as e:
            self._dy2static_error = e
        if self._is_layer:
            self._jitted = jax.jit(self._layer_core)
        else:
            self._jitted = jax.jit(self._fn_core)

    def _override_ctx(self):
        """Apply the dy2static-converted forward to the layer for the
        duration of a traced call only — the user's eager object stays
        untouched (a permanent rebind would silently change eager behavior
        and freeze closure nonlocals)."""
        import contextlib
        import types as _types

        if self._forward_override is None or not self._is_layer:
            return _nullcontext()

        @contextlib.contextmanager
        def ctx():
            old = self._target.__dict__.get("forward")
            self._target.__dict__["forward"] = _types.MethodType(
                self._forward_override, self._target)
            try:
                yield
            finally:
                if old is None:
                    self._target.__dict__.pop("forward", None)
                else:
                    self._target.__dict__["forward"] = old
        return ctx()

    def _layer_core(self, state, rng, args, kwargs):
        with self._override_ctx():
            out, new_state = functional_call(self._target, state, *args,
                                             rng=rng, **kwargs)
        return out, new_state

    def _fn_core(self, rng, args, kwargs):
        with no_grad(), _rnd.key_stream(rng):
            out = self._target(*_wrap_tree(args), **_wrap_tree(kwargs))
        return _unwrap_tree(out)

    def __call__(self, *args, **kwargs):
        rng = _rnd.next_key()
        args_a = _unwrap_tree(args)
        kwargs_a = _unwrap_tree(kwargs)
        if self._is_layer:
            state = self._target.functional_state()
            out, new_state = self._jitted(state, rng, args_a, kwargs_a)
            self._target.load_functional_state(new_state)
            return _wrap_tree(out)
        return _wrap_tree(self._jitted(rng, args_a, kwargs_a))

    # introspection API parity
    @property
    def code(self):
        import inspect
        try:
            return inspect.getsource(
                self._target.forward if self._is_layer else self._target)
        except Exception:
            return "<source unavailable>"

    def concrete_program(self):
        return self._jitted


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """paddle.jit.to_static equivalent: compile a Layer/function via jax.jit."""
    def deco(target):
        return StaticFunction(target, input_spec, build_strategy, backend)
    if function is not None:
        return deco(function)
    return deco


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def save(layer, path, input_spec=None, **config):
    """paddle.jit.save equivalent (reference: jit/api.py save → dy2static →
    save_inference_model).  Exports a standalone executable artifact via
    jax.export; loadable with :func:`load` WITHOUT the original class."""
    from ..static import save_inference_model

    target = layer._target if isinstance(layer, StaticFunction) else layer
    if not isinstance(target, Layer):
        raise TypeError("jit.save expects a Layer or to_static(Layer); "
                        "got %r" % (type(layer).__name__,))
    if input_spec is None:
        input_spec = getattr(layer, "_input_spec", None)
    if input_spec is None:
        raise ValueError("jit.save needs input_spec=[InputSpec(...), ...] "
                         "(shapes are static under XLA)")
    ctx = (layer._override_ctx() if isinstance(layer, StaticFunction)
           else _nullcontext())
    with ctx:
        return save_inference_model(path, model=target,
                                    input_spec=input_spec, **config)


class TranslatedLayer(Layer):
    """The loaded-artifact Layer (reference: fluid/dygraph/io.py
    TranslatedLayer): callable like a Layer, runs the deserialized exported
    program; no original class needed."""

    def __init__(self, predictor):
        super().__init__()
        self._predictor = predictor

    def forward(self, *args):
        return self._predictor(*args)


def load(path, **config):
    """paddle.jit.load equivalent: returns a callable TranslatedLayer running
    the serialized StableHLO module."""
    from ..static import load_inference_model

    predictor = load_inference_model(path, **config)
    return TranslatedLayer(predictor)


class TrainStep:
    """One fused, compiled training step: forward + backward + optimizer.

    The TPU-native Executor: what the reference splits across
    Tracer/autograd/optimizer ops scheduled by InterpreterCore
    (framework/new_executor/interpretercore.cc) is here ONE XLA program —
    loss, grads (jax.grad), update — with every elementwise chain fused.

    Batch convention: ``step(*batch)`` sends ``batch[:num_inputs]`` to the
    model and the rest (labels) to ``loss_fn(*outputs, *labels)`` — all as
    traced arguments, so every batch is fresh data to the same compiled
    program.

    Usage:
        step = TrainStep(model, loss_fn, opt)
        for x, y in loader:
            loss = step(x, y)
        step.sync_to_model()   # write trained arrays back into model/opt
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer,
                 num_inputs: int = 1, in_shardings=None, donate=True,
                 zero_stage: Optional[int] = None, zero_axis: str = "sdp"):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.num_inputs = num_inputs
        full_state = model.functional_state()
        trainable = {name for name, p in model.named_parameters()
                     if not p.stop_gradient}
        # copy so the first donated step cannot invalidate the eager model's
        # own buffers
        copy = (lambda v: jnp.array(v)) if donate else (lambda v: v)
        self.params = {k: copy(v) for k, v in full_state.items()
                       if k in trainable}
        self.buffers = {k: copy(v) for k, v in full_state.items()
                        if k not in trainable}
        # AMP O2: a low-precision trainable param is held as ONE fp32
        # master array in the step state and cast to its compute dtype
        # inside the compiled step (so the optimizer never creates a
        # separate "master" slot).  Keeping both a bf16 param and an fp32
        # master in the step I/O round-trips every parameter through HBM
        # twice per step — neither buffer can donation-alias the other —
        # measured ~15 ms/step of pure copies on the GPT-2 345M bench
        # (PERF.md "copy lane").
        self._compute_dtypes = {}
        if getattr(optimizer, "_multi_precision", None) is not False:
            for k, v in list(self.params.items()):
                if hasattr(v, "dtype") and v.dtype in (jnp.bfloat16,
                                                       jnp.float16):
                    self._compute_dtypes[k] = v.dtype
                    self.params[k] = v.astype(jnp.float32)
        self.opt_state = optimizer.init_state(self.params)
        self._dirty = True
        self._step_index = -1  # host-side step counter (faultpoint ctx)

        # ---- ZeRO placement (reference semantics: sharding_stage2.py:43
        # grad reduce-scatter, sharding_stage3.py:50 param slicing;
        # TPU-native: shardings + GSPMD, SURVEY.md §7 table) ----------------
        self._zero_stage = zero_stage
        self._zero_axis = zero_axis
        self._param_specs = None
        self._grad_specs = None
        self._in_shardings = in_shardings
        if zero_stage:
            from ..distributed import mesh as _mesh
            from ..distributed.sharding import _stage_spec_for
            from jax.sharding import NamedSharding, PartitionSpec

            mesh = _mesh.ensure_mesh()
            if _mesh.axis_size(zero_axis) <= 1 and mesh.size > 1:
                raise ValueError(
                    "zero_stage=%d requested but mesh axis %r has size <= 1 "
                    "(mesh axes: %s) — init_mesh({'%s': N, ...}) first or "
                    "the sharding would silently be a no-op"
                    % (zero_stage, zero_axis, dict(
                        zip(mesh.axis_names, mesh.devices.shape)),
                       zero_axis))
            shard = lambda a: _stage_spec_for(a, zero_axis)
            # stage >=1: optimizer slots sharded
            def place_slot(x):
                if hasattr(x, "ndim") and x.ndim > 0:
                    return jax.device_put(
                        x, NamedSharding(mesh, shard(x)))
                return x
            self.opt_state = jax.tree_util.tree_map(place_slot,
                                                    self.opt_state)
            # stage >=2: grads reduce-scattered onto the same layout
            if zero_stage >= 2:
                self._grad_specs = {k: shard(v)
                                    for k, v in self.params.items()}
            # stage 3: parameters themselves sharded (allgather-on-use)
            if zero_stage >= 3:
                self._param_specs = {k: shard(v)
                                     for k, v in self.params.items()}
                self.params = {
                    k: jax.device_put(
                        v, NamedSharding(mesh, self._param_specs[k]))
                    for k, v in self.params.items()}
            else:
                self.params = {
                    k: jax.device_put(
                        v, NamedSharding(mesh, PartitionSpec()))
                    for k, v in self.params.items()}
            self._mesh = mesh
        elif in_shardings is not None:
            from ..distributed import mesh as _mesh
            self._mesh = _mesh.ensure_mesh()
        else:
            self._mesh = None

        def loss_core(params, buffers, rng, batch):
            if self._compute_dtypes:
                # fp32 master -> compute dtype; the cast's vjp upcasts the
                # bf16 grads back to f32 for the optimizer update
                params = {k: (p.astype(self._compute_dtypes[k])
                              if k in self._compute_dtypes else p)
                          for k, p in params.items()}
            state = {**params, **buffers}
            self.model.train()
            inputs = batch[:self.num_inputs]
            labels = batch[self.num_inputs:]
            out, new_state = functional_call(self.model, state, *inputs,
                                             rng=rng)
            outs = out if isinstance(out, tuple) else (out,)
            with no_grad():  # jax traces the grad; keep the eager tape off
                loss = self.loss_fn(
                    *[Tensor(o) if not isinstance(o, Tensor) else o
                      for o in outs],
                    *[Tensor(l) if not isinstance(l, Tensor) else l
                      for l in labels])
            if isinstance(loss, Tensor):
                loss = loss._array
            new_buffers = {k: new_state[k] for k in buffers.keys()}
            return loss, new_buffers

        def grads_core(params, buffers, rng, batch):
            (loss, new_buffers), grads = jax.value_and_grad(
                loss_core, has_aux=True)(params, buffers, rng, batch)
            if self._grad_specs is not None:
                # ZeRO stage-2: constrain each grad to the slot layout so
                # GSPMD lowers the data-parallel grad sum to reduce-scatter
                # (sharding_stage2.py:43 semantics)
                from jax.sharding import NamedSharding
                grads = {
                    k: jax.lax.with_sharding_constraint(
                        g, NamedSharding(self._mesh, self._grad_specs[k]))
                    for k, g in grads.items()}
            return loss, new_buffers, grads

        # exposed for tests/diagnostics: the exact grad computation the
        # compiled step runs, including ZeRO layout constraints
        self._grads_core = grads_core

        # opt-in grad-norm telemetry: computes the global grad norm inside
        # the compiled step and publishes it as a gauge.  Costs one extra
        # reduction in-program plus ONE device sync per step on the host —
        # that is why it is an env opt-in, not a default
        import os as _env_os
        self._emit_grad_norm = _env_os.environ.get(
            "PADDLE_TPU_METRICS_GRAD_NORM", "0") not in ("0", "", "off")

        def step_fn(params, buffers, opt_state, lr, rng, batch):
            loss, new_buffers, grads = grads_core(params, buffers, rng,
                                                  batch)
            with _scopes.scope(_scopes.OPTIMIZER):
                new_params, new_opt_state = self.optimizer.apply_gradients(
                    params, grads, opt_state, lr)
            if self._param_specs is not None:
                # ZeRO stage-3: updated params stay sharded
                from jax.sharding import NamedSharding
                new_params = {
                    k: jax.lax.with_sharding_constraint(
                        p, NamedSharding(self._mesh, self._param_specs[k]))
                    for k, p in new_params.items()}
            if self._emit_grad_norm:
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree_util.tree_leaves(grads)))
                return loss, new_params, new_buffers, new_opt_state, gnorm
            return loss, new_params, new_buffers, new_opt_state

        donate_args = (0, 1, 2) if donate else ()
        # recorded for the trace-tier donation audit (TPU502 in
        # paddle_tpu.analysis.trace): the registry lowers self._step with
        # trace_args() and verifies each declared donation materializes
        # as input-output aliasing in the compiled entry
        self._donate_argnums = donate_args
        self._step_fn = step_fn   # un-jitted, for audit re-wraps
        # recompile watchdog: one TrainStep is one program — a second
        # compile means a batch shape/dtype is churning underneath the
        # caller (observability.watchdog warns; strict mode raises)
        from ..observability import registry as _obs
        from ..observability.watchdog import watch
        self._step = watch("jit.train_step",
                           jax.jit(step_fn, donate_argnums=donate_args,
                                   out_shardings=self._pin_state_layout()),
                           expected=1)
        self._m_step_seconds = _obs.histogram("train.step_seconds")
        self._m_steps = _obs.counter("train.steps")
        self._m_grad_norm = _obs.gauge("train.grad_norm")
        # fetched once; the NOOP_BEACON singleton when liveness is off
        self._beacon = _liveness.beacon("train.step")

    def _pin_state_layout(self):
        """When the carried state (params, buffers, optimizer state) lives
        on a multi-device mesh: put any leaf still off the mesh onto it
        (replicated) and return the ``out_shardings`` that hand the state
        back in exactly the layout it is fed in.  None (compiler's choice)
        on one device.  Without the pin GSPMD hands the state back under an
        equivalent but differently spelled sharding (``P('mp')`` for a
        declared ``P('mp', None)``), the second call misses the jit cache
        and the "compile-once" step compiles twice."""
        from jax.sharding import NamedSharding, PartitionSpec
        from ..optimizer.optimizer import mesh_of
        state = (self.params, self.buffers, self.opt_state)
        meshes = [m for m in map(mesh_of, jax.tree_util.tree_leaves(state))
                  if m is not None]
        if not meshes:
            return None
        rep = NamedSharding(meshes[0], PartitionSpec())
        self.params, self.buffers, self.opt_state = jax.tree_util.tree_map(
            lambda a: a if mesh_of(a) is not None else
            jax.device_put(a, rep), state)
        pinned = jax.tree_util.tree_map(
            lambda a: a.sharding,
            (self.params, self.buffers, self.opt_state))
        return (None,) + pinned + ((None,) if self._emit_grad_norm else ())

    def trace_args(self, batch):
        """The exact argument tuple ``self._step`` runs with, for
        lowering/audit (``self._step.lower(*step.trace_args(batch))``).
        ``batch`` is the tuple a normal ``step(*batch)`` call would take.

        Uses a FIXED key rather than drawing from the global stream: the
        result is only lowered, never executed, and auditing a live step
        must not shift every subsequent dropout mask of the real run.
        ``jax.random.key`` (typed) matches the aval the production
        ``__call__`` passes, so the audit lowers the identical program."""
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        rng = jax.random.key(0)
        return (self.params, self.buffers, self.opt_state, lr, rng,
                _unwrap_tree(tuple(batch)))

    def cost_report(self, batch):
        """XLA cost/memory analysis of THIS step's compiled program
        (:class:`paddle_tpu.observability.costs.ProgramReport`) — the
        bench `cost` block's source.  Lowers + compiles once per call
        (the jit dispatch cache is separate from the AOT path): cold
        path only — bench.py calls it after the timed loop."""
        from ..observability import costs as _costs
        compiled = jax.jit(self._step_fn,
                           donate_argnums=self._donate_argnums) \
            .lower(*self.trace_args(batch)).compile()
        return _costs.report_from_compiled("jit.train_step", compiled)

    def __call__(self, *batch):
        rng = _rnd.next_key()
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        batch_a = _unwrap_tree(batch)
        # chaos hook: fires per step on the HOST side (a faultpoint inside
        # the jitted step_fn would be traced away); a NaNBatch action
        # poisons one input so loss and every grad behind it go NaN —
        # the deterministic "NaN grads at step k" injection
        self._step_index += 1
        ctx = faultpoint("train.grads", batch=batch_a,
                         step=self._step_index)
        if ctx is not None:
            batch_a = ctx["batch"]
        if self._in_shardings is not None and self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            specs = self._in_shardings
            # PartitionSpec IS a tuple: without the explicit check a single
            # spec like PartitionSpec("sdp") would be unpacked into one
            # raw axis-name STRING per batch element, which NamedSharding
            # rejects
            if isinstance(specs, PartitionSpec) or not isinstance(
                    specs, (list, tuple)):
                specs = [specs] * len(batch_a)
            batch_a = tuple(
                jax.device_put(b, NamedSharding(self._mesh, s))
                for b, s in zip(batch_a, specs))
        import time as _time
        t0 = _time.perf_counter()
        with self._beacon:   # liveness: a hang inside the step is a stall
            with _tracing.annotation("train", "step_call"):
                out = self._step(
                    self.params, self.buffers, self.opt_state, lr, rng,
                    batch_a)
            if self._emit_grad_norm:
                loss, self.params, self.buffers, self.opt_state, gnorm \
                    = out
                self._m_grad_norm.set(float(gnorm))  # opt-in: syncs step
            else:
                loss, self.params, self.buffers, self.opt_state = out
        self._m_step_seconds.observe(_time.perf_counter() - t0)
        self._m_steps.inc()
        self._dirty = True
        if isinstance(self.optimizer._learning_rate, object) and hasattr(
                self.optimizer._learning_rate, "step"):
            try:
                self.optimizer._learning_rate.step()
            except TypeError:
                pass
        return Tensor(loss)

    def sync_to_model(self):
        """Write the trained arrays back into the eager model."""
        params = {k: (v.astype(self._compute_dtypes.get(k, v.dtype))
                      if hasattr(v, "dtype") else v)
                  for k, v in self.params.items()}
        self.model.load_functional_state({**params, **self.buffers})
        self._dirty = False

    # -- checkpoint contract (incubate.checkpoint) -------------------------
    def state_dict(self):
        """Everything needed to resume: params, buffers, optimizer slots,
        and the LR-scheduler/optimizer bookkeeping."""
        opt_extra = {}
        lr = self.optimizer._learning_rate
        if hasattr(lr, "state_dict"):
            opt_extra["lr_scheduler"] = lr.state_dict()
        return {"params": dict(self.params), "buffers": self.buffers,
                "opt_state": self.opt_state, "opt_extra": opt_extra}

    def set_state_dict(self, state):
        """Restore from :meth:`state_dict` output.  Arrays are re-placed on
        their current shardings (ZeRO layouts survive a restore)."""
        def place_like(new, old):
            if hasattr(old, "sharding") and hasattr(new, "shape"):
                # COPY (jnp.array), never alias: the incoming state may
                # reference another live TrainStep's buffers (state_dict
                # returns views), and this step's donation would delete
                # them out from under their owner
                arr = jnp.array(new)
                if hasattr(old, "dtype") and arr.dtype != old.dtype:
                    # e.g. a bf16 model-side save restored into the fp32
                    # master param state
                    arr = arr.astype(old.dtype)
                return jax.device_put(arr, old.sharding)
            return new
        self.params = {k: place_like(v, self.params.get(k))
                       for k, v in state["params"].items()}
        self.buffers = {k: place_like(v, self.buffers.get(k))
                        for k, v in state["buffers"].items()}
        self.opt_state = jax.tree_util.tree_map(
            place_like, state["opt_state"], self.opt_state)
        lr = self.optimizer._learning_rate
        sched = state.get("opt_extra", {}).get("lr_scheduler")
        if sched is not None and hasattr(lr, "set_state_dict"):
            lr.set_state_dict(sched)
        self._dirty = True
