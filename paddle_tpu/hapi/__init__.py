"""High-level API (reference surface: python/paddle/hapi/model.py —
Model.prepare/fit/evaluate/predict at model.py:907,1486,1557; callbacks).

TPU-native: fit() drives a jitted TrainStep (one XLA program per step) rather
than the reference's per-op dygraph/static adapters.
"""
from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from ..core.tensor import Tensor
from ..io import DataLoader, Dataset
from ..jit import TrainStep, functional_call
from ..metric import Metric
from ..observability import hbm as _hbm
from ..observability import liveness as _liveness
from ..observability import registry as _metrics

# liveness beacon over one fit batch (train_batch INCLUDES the loss
# fetch — a real device sync, so a wedged device step stalls here even
# when dispatch itself returned)
_liveness.declare_beacon(
    "train.fit_batch", "one hapi fit batch: compiled step dispatch + "
    "the loss fetch device sync", deadline=600.0)

__all__ = ["Model", "Callback", "ProgBarLogger", "ModelCheckpoint",
           "EarlyStopping", "LRScheduler", "summary", "flops"]


class Callback:
    def __init__(self):
        self.model = None
        self.params = {}

    def set_model(self, model):
        self.model = model

    def set_params(self, params):
        self.params = params or {}

    def on_train_begin(self, logs=None):
        pass

    def on_train_end(self, logs=None):
        pass

    def on_epoch_begin(self, epoch, logs=None):
        pass

    def on_epoch_end(self, epoch, logs=None):
        pass

    def on_train_batch_begin(self, step, logs=None):
        pass

    def on_train_batch_end(self, step, logs=None):
        pass

    def on_eval_begin(self, logs=None):
        pass

    def on_eval_end(self, logs=None):
        pass

    def on_eval_batch_end(self, step, logs=None):
        pass


class ProgBarLogger(Callback):
    def __init__(self, log_freq=10, verbose=2):
        super().__init__()
        self.log_freq = log_freq
        self.verbose = verbose

    def on_epoch_begin(self, epoch, logs=None):
        self._epoch = epoch
        self._t0 = time.time()

    def on_train_batch_end(self, step, logs=None):
        if self.verbose and step % self.log_freq == 0:
            items = ", ".join(f"{k}: {v:.4f}" if isinstance(v, float) else
                              f"{k}: {v}" for k, v in (logs or {}).items())
            print(f"Epoch {self._epoch} step {step}: {items}")

    def on_epoch_end(self, epoch, logs=None):
        if self.verbose:
            dt = time.time() - self._t0
            items = ", ".join(f"{k}: {v:.4f}" if isinstance(v, float) else
                              f"{k}: {v}" for k, v in (logs or {}).items())
            print(f"Epoch {epoch} done in {dt:.1f}s: {items}")


class ModelCheckpoint(Callback):
    def __init__(self, save_freq=1, save_dir=None):
        super().__init__()
        self.save_freq = save_freq
        self.save_dir = save_dir

    def on_epoch_end(self, epoch, logs=None):
        if self.save_dir and epoch % self.save_freq == 0:
            self.model.save(f"{self.save_dir}/epoch_{epoch}")


class EarlyStopping(Callback):
    def __init__(self, monitor="loss", mode="min", patience=0, min_delta=0,
                 baseline=None, save_best_model=True):
        super().__init__()
        self.monitor = monitor
        self.mode = mode
        self.patience = patience
        self.min_delta = min_delta
        self.best = baseline
        self.wait = 0
        self.stop_training = False

    def on_eval_end(self, logs=None):
        cur = (logs or {}).get(self.monitor)
        if cur is None:
            return
        cur = float(cur[0] if isinstance(cur, (list, tuple)) else cur)
        better = (self.best is None
                  or (self.mode == "min" and cur < self.best - self.min_delta)
                  or (self.mode == "max" and cur > self.best + self.min_delta))
        if better:
            self.best = cur
            self.wait = 0
        else:
            self.wait += 1
            if self.wait >= self.patience:
                self.stop_training = True
                self.model.stop_training = True


class LRScheduler(Callback):
    def __init__(self, by_step=True, by_epoch=False):
        super().__init__()
        self.by_step = by_step
        self.by_epoch = by_epoch


class Model:
    """reference parity: python/paddle/hapi/model.py:907."""

    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self.stop_training = False
        self._optimizer = None
        self._loss = None
        self._metrics: List[Metric] = []
        self._train_step = None

    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        if metrics is None:
            self._metrics = []
        else:
            self._metrics = metrics if isinstance(metrics, (list, tuple)) \
                else [metrics]

    def _ensure_train_step(self):
        if self._train_step is None:
            def loss_fn(logits, *rest):
                raise RuntimeError  # replaced per-batch below
            self._train_step = None  # built lazily in train_batch

    def train_batch(self, inputs, labels=None, update=True):
        """One eager-compiled step (reference: model.py:1045)."""
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        labels = labels if labels is None or isinstance(labels, (list, tuple)) \
            else [labels]
        if self._train_step is None:
            self._train_step = TrainStep(self.network, self._loss,
                                         self._optimizer,
                                         num_inputs=len(inputs))
        loss = self._train_step(*inputs, *(labels or []))
        metrics_out = []
        return [float(loss.numpy())], metrics_out

    def eval_batch(self, inputs, labels=None):
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        labels = labels if labels is None or isinstance(labels, (list, tuple)) \
            else [labels]
        self.network.eval()
        if self._train_step is not None:
            self._train_step.sync_to_model()
        outs = self.network(*inputs)
        outs_t = outs if isinstance(outs, (list, tuple)) else [outs]
        loss = None
        if self._loss is not None and labels:
            loss = self._loss(*(list(outs_t) + list(labels)))
        metric_res = []
        for m in self._metrics:
            c = m.compute(*(list(outs_t) + list(labels or [])))
            metric_res.append(m.update(c))
        self.network.train()
        if loss is not None:
            return [float(loss.numpy())], metric_res
        return metric_res

    def predict_batch(self, inputs):
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        self.network.eval()
        if self._train_step is not None:
            self._train_step.sync_to_model()
        out = self.network(*inputs)
        self.network.train()
        return out

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None):
        """reference parity: model.py:1557."""
        if isinstance(train_data, Dataset):
            train_loader = DataLoader(train_data, batch_size=batch_size,
                                      shuffle=shuffle, drop_last=drop_last,
                                      num_workers=num_workers)
        else:
            train_loader = train_data
        eval_loader = None
        if eval_data is not None:
            eval_loader = (DataLoader(eval_data, batch_size=batch_size)
                           if isinstance(eval_data, Dataset) else eval_data)
        cbs = list(callbacks or [])
        if verbose:
            cbs.append(ProgBarLogger(log_freq, verbose))
        if save_dir:
            cbs.append(ModelCheckpoint(save_freq, save_dir))
        for cb in cbs:
            cb.set_model(self)
            cb.set_params({"epochs": epochs, "verbose": verbose})
        for cb in cbs:
            cb.on_train_begin()
        # fit-loop telemetry (OBSERVABILITY.md): per-batch wall time here
        # includes the loss fetch in train_batch — a real device sync — so
        # unlike train.step_seconds (dispatch only) this is end-to-end
        m_batch = _metrics.histogram("train.batch_seconds")
        m_loss = _metrics.gauge("train.loss")
        m_samples = _metrics.counter("train.samples")
        m_tokens = _metrics.counter("train.tokens")
        b_batch = _liveness.beacon("train.fit_batch")
        it_count = 0
        for epoch in range(epochs):
            for cb in cbs:
                cb.on_epoch_begin(epoch)
            logs = {}
            for step, batch in enumerate(train_loader):
                ins, lbls = self._split_batch(batch)
                t0 = time.perf_counter()
                with b_batch:
                    losses, _ = self.train_batch(ins, lbls)
                m_batch.observe(time.perf_counter() - t0)
                m_loss.set(losses[0])
                shape = getattr(ins[0], "shape", None)
                if shape:
                    m_samples.inc(int(shape[0]))
                    if len(shape) >= 2:
                        m_tokens.inc(int(shape[0]) * int(shape[1]))
                logs = {"loss": losses[0]}
                # HBM-ledger sample at the batch boundary (the loss
                # fetch above was a real device sync, so live_arrays is
                # settled here); one global None check while disarmed
                _hbm.maybe_sample("train.batch")
                for cb in cbs:
                    cb.on_train_batch_end(step, logs)
                it_count += 1
                # stop_training is honored PER BATCH: a callback tripping
                # mid-epoch (e.g. DivergenceMonitor with its rollback ring
                # exhausted) must not keep training — and then checkpoint —
                # a contaminated state for the rest of a long epoch
                if self.stop_training or (num_iters and
                                          it_count >= num_iters):
                    break
            if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                eval_logs = self.evaluate(eval_loader, verbose=0)
                logs.update(eval_logs)
                for cb in cbs:
                    cb.on_eval_end(eval_logs)
            for cb in cbs:
                cb.on_epoch_end(epoch, logs)
            if self.stop_training or (num_iters and it_count >= num_iters):
                break
        for cb in cbs:
            cb.on_train_end()
        if self._train_step is not None:
            self._train_step.sync_to_model()

    @staticmethod
    def _split_batch(batch):
        if isinstance(batch, (list, tuple)):
            if len(batch) >= 2:
                return [batch[0]], list(batch[1:])
            return [batch[0]], []
        return [batch], []

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None):
        loader = (DataLoader(eval_data, batch_size=batch_size,
                             num_workers=num_workers)
                  if isinstance(eval_data, Dataset) else eval_data)
        for m in self._metrics:
            m.reset()
        losses = []
        for batch in loader:
            ins, lbls = self._split_batch(batch)
            res = self.eval_batch(ins, lbls)
            if isinstance(res, tuple) and len(res) == 2 and res[0]:
                losses.append(res[0][0])
        logs = {}
        if losses:
            logs["eval_loss"] = float(np.mean(losses))
        for m in self._metrics:
            logs["eval_" + m.name()] = m.accumulate()
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, callbacks=None, verbose=1):
        loader = (DataLoader(test_data, batch_size=batch_size,
                             num_workers=num_workers)
                  if isinstance(test_data, Dataset) else test_data)
        outs = []
        for batch in loader:
            ins, _ = self._split_batch(batch)
            outs.append(self.predict_batch(ins))
        return outs

    def save(self, path, training=True):
        from .. import framework
        if self._train_step is not None:
            self._train_step.sync_to_model()
        framework.save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            framework.save(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from .. import framework
        sd = framework.load(path + ".pdparams")
        self.network.set_state_dict(sd)
        import os
        if (not reset_optimizer and self._optimizer is not None
                and os.path.exists(path + ".pdopt")):
            self._optimizer.set_state_dict(framework.load(path + ".pdopt"))
        self._train_step = None

    def parameters(self):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        return summary(self.network, input_size, dtype)


def summary(net, input_size=None, dtypes=None):
    """Parameter-count summary (reference: hapi/model_summary.py)."""
    total = 0
    trainable = 0
    lines = [f"{'Layer':45s} {'Param #':>12s}"]
    for name, p in net.named_parameters():
        n = int(np.prod(p.shape)) if p.shape else 1
        total += n
        if not p.stop_gradient:
            trainable += n
        lines.append(f"{name[:45]:45s} {n:12d}")
    lines.append(f"Total params: {total:,}")
    lines.append(f"Trainable params: {trainable:,}")
    print("\n".join(lines))
    return {"total_params": total, "trainable_params": trainable}


def flops(net, input_size=None, inputs=None, dtypes=None, custom_ops=None,
          print_detail=False):
    """Model FLOPs (reference: hapi/dynamic_flops.py paddle.flops).

    TPU-native: instead of per-layer-type formulas, the forward is traced
    and compiled and XLA's own cost analysis reports the FLOPs of the
    compiled graph (fusions included).  Limitation: custom-call regions
    (Pallas kernels) are opaque to XLA cost analysis and count as 0;
    ``custom_ops`` hooks are therefore not supported — measure such models
    with the profiler instead (PERF.md methodology).

    ``dtypes``: one dtype string or a list matching input_size (default
    float32) — integer-input models (Embedding-first) need e.g. "int32".
    """
    import jax

    from ..core.dtype import convert_dtype

    if custom_ops is not None:
        raise NotImplementedError(
            "flops(custom_ops=...) is not supported on the TPU build: XLA "
            "cost analysis counts compiled HLO only (custom Pallas calls "
            "are opaque); use jax.profiler / PERF.md methodology instead")
    if inputs is None:
        if input_size is None:
            raise ValueError("flops needs input_size=[shape, ...] or inputs")
        shapes = input_size if isinstance(input_size[0], (list, tuple)) \
            else [input_size]
        if dtypes is None:
            dts = ["float32"] * len(shapes)
        elif isinstance(dtypes, str):
            dts = [dtypes] * len(shapes)
        else:
            dts = list(dtypes)
            if len(dts) != len(shapes):
                raise ValueError(
                    f"dtypes has {len(dts)} entries for {len(shapes)} "
                    "input shapes")
        inputs = [jax.ShapeDtypeStruct(tuple(int(d) for d in s),
                                       convert_dtype(dt))
                  for s, dt in zip(shapes, dts)]
    else:
        inputs = [i._array if hasattr(i, "_array") else i for i in inputs]
    was_training = getattr(net, "training", True)
    net.eval()
    try:
        state = net.functional_state()

        def fwd(state, *args):
            out, _ = functional_call(net, state, *args)
            return out

        compiled = jax.jit(fwd).lower(state, *inputs).compile()
    finally:
        if was_training:
            net.train()
    # ONE cost_analysis parser for the whole repo:
    # observability.costs — the same extraction the
    # `programs` CLI and TPU506 run on the canonical registry.  strict:
    # a RAISING cost_analysis must propagate (this API returns a bare
    # int — a swallowed failure would read as "0 FLOPs", a plausible
    # wrong answer with no degradation channel)
    from ..observability.costs import cost_analysis_dict
    ca = cost_analysis_dict(compiled, strict=True)
    total = int(ca.get("flops", 0))
    if print_detail:
        print(f"FLOPs (XLA cost analysis): {total:,}")
        if "bytes accessed" in ca:
            print(f"Bytes accessed: {int(ca['bytes accessed']):,}")
    return total
