"""Loss functionals (reference: python/paddle/nn/functional/loss.py).

cross_entropy follows the reference's fused softmax+CE semantics
(paddle/phi/kernels/gpu/cross_entropy_kernel.cu): computed from logits with a
numerically stable log-softmax, supporting soft labels, ignore_index and
class weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import call, wrap_op
from ...core.dtype import x64_scope


def _reduce(out, reduction, weight_sum=None):
    if reduction == "mean":
        if weight_sum is not None:
            return jnp.sum(out) / jnp.maximum(weight_sum, 1e-12)
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def softmax_with_cross_entropy_raw(logits, label, soft_label=False,
                                   ignore_index=-100, axis=-1):
    # f32 softmax statistics regardless of logits dtype (bf16 logits over a
    # 50k vocab lose the tail mass); XLA fuses the convert into the reduce
    if soft_label:
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
        return -jnp.sum(label * logp, axis=axis)
    # hard labels: nll = logsumexp(logits) - logits[label].  Two streaming
    # reductions over the bf16 logits instead of materialising the full
    # (..., V) f32 log_softmax (for a GPT vocab that array is GBs of HBM
    # traffic; measured ~4ms/step off the 345M bench)
    lbl = label
    if lbl.ndim == logits.ndim and lbl.shape[axis] == 1:
        lbl = jnp.squeeze(lbl, axis)
    # keep every elementwise use of `logits` in its own consumer fusion:
    # binding `lf = logits.astype(f32)` once made XLA CSE the convert and
    # MATERIALISE the full f32 logits (1.65 GB at GPT-2 bench shapes,
    # ~10 ms/step of HBM traffic); with per-consumer converts the bf16
    # matmul output is the only materialised array and each streaming
    # reduction fuses its own upcast
    m = jax.lax.stop_gradient(jnp.max(logits, axis=axis))
    mf = m.astype(jnp.float32)
    lse = mf + jnp.log(jnp.sum(
        jnp.exp(logits.astype(jnp.float32) - jnp.expand_dims(mf, axis)),
        axis=axis))
    # cast BEFORE the clip so every index op is i32: s64 labels would
    # otherwise put emulated 64-bit clamp/compare ops into the TPU program
    # (caught by tests/test_x64_audit.py)
    idx = jnp.clip(lbl.astype(jnp.int32), 0, logits.shape[axis] - 1)
    # promise_in_bounds is honest (idx just got clipped); the gather itself
    # traces with x64 off because take_along_axis widens its indices to
    # the default int, which is s64 under the global x64 mode
    with x64_scope(False):
        t = jnp.take_along_axis(logits, jnp.expand_dims(idx, axis),
                                axis=axis, mode="promise_in_bounds")
    t = t.astype(jnp.float32)
    nll = lse - jnp.squeeze(t, axis)
    mask = (lbl != ignore_index)
    return jnp.where(mask, nll, 0.0)


@wrap_op
def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0):
    logits = input
    nclass = logits.shape[axis]
    if label_smoothing > 0.0:
        if not soft_label:
            onehot = jax.nn.one_hot(
                label if label.ndim < logits.ndim else jnp.squeeze(label, axis),
                nclass, dtype=logits.dtype, axis=axis)
            label = onehot
            soft_label = True
        label = label * (1 - label_smoothing) + label_smoothing / nclass
    if not use_softmax:
        # input is already a probability distribution
        logp = jnp.log(jnp.maximum(input, 1e-30))
        if soft_label:
            out = -jnp.sum(label * logp, axis=axis)
            return _reduce(out, reduction)
        lbl = label if label.ndim < input.ndim else jnp.squeeze(label, axis)
        out = -jnp.take_along_axis(logp, jnp.expand_dims(lbl, axis), axis=axis)
        out = jnp.squeeze(out, axis)
        return _reduce(out, reduction)
    out = softmax_with_cross_entropy_raw(logits, label, soft_label,
                                         ignore_index, axis)
    if weight is not None and not soft_label:
        lbl = label if label.ndim < logits.ndim else jnp.squeeze(label, axis)
        w = jnp.take(weight, jnp.clip(lbl, 0, nclass - 1))
        w = jnp.where(lbl != ignore_index, w, 0.0)
        out = out * w
        return _reduce(out, reduction, weight_sum=jnp.sum(w))
    if reduction == "mean" and not soft_label:
        lbl = label if label.ndim < logits.ndim else jnp.squeeze(label, axis)
        valid = (lbl != ignore_index).astype(out.dtype)
        return jnp.sum(out) / jnp.maximum(jnp.sum(valid), 1.0)
    return _reduce(out, reduction)


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, axis=-1,
                               return_softmax=False):
    def raw(lg, lb):
        loss = softmax_with_cross_entropy_raw(lg, lb, soft_label, ignore_index, axis)
        loss = jnp.expand_dims(loss, axis)
        if return_softmax:
            return loss, jax.nn.softmax(lg, axis=axis)
        return loss
    return call(raw, logits, label, name="softmax_with_cross_entropy")


@wrap_op
def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean"):
    nll = -jnp.take_along_axis(input, jnp.expand_dims(label, 1), axis=1)
    nll = jnp.squeeze(nll, 1)
    mask = label != ignore_index
    if weight is not None:
        w = jnp.take(weight, jnp.clip(label, 0, input.shape[1] - 1))
        w = jnp.where(mask, w, 0.0)
        nll = nll * w
        if reduction == "mean":
            return jnp.sum(nll) / jnp.maximum(jnp.sum(w), 1e-12)
    nll = jnp.where(mask, nll, 0.0)
    if reduction == "mean":
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask.astype(nll.dtype)), 1.0)
    return _reduce(nll, reduction)


@wrap_op
def mse_loss(input, label, reduction="mean"):
    return _reduce(jnp.square(input - label), reduction)


@wrap_op
def l1_loss(input, label, reduction="mean"):
    return _reduce(jnp.abs(input - label), reduction)


@wrap_op
def smooth_l1_loss(input, label, reduction="mean", delta=1.0):
    d = jnp.abs(input - label)
    out = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
    # paddle multiplies by delta
    out = out * delta
    return _reduce(out, reduction)


@wrap_op
def binary_cross_entropy(input, label, weight=None, reduction="mean"):
    eps = 1e-12
    out = -(label * jnp.log(jnp.maximum(input, eps))
            + (1 - label) * jnp.log(jnp.maximum(1 - input, eps)))
    if weight is not None:
        out = out * weight
    return _reduce(out, reduction)


@wrap_op
def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None):
    max_val = jnp.maximum(-logit, 0.0)
    if pos_weight is not None:
        log_w = (pos_weight - 1.0) * label + 1.0
        out = (1 - label) * logit + log_w * (
            jnp.log1p(jnp.exp(-jnp.abs(logit))) + max_val)
    else:
        out = (1 - label) * logit + jnp.log1p(jnp.exp(-jnp.abs(logit))) + max_val
    if weight is not None:
        out = out * weight
    return _reduce(out, reduction)


@wrap_op
def kl_div(input, label, reduction="mean"):
    out = label * (jnp.log(jnp.maximum(label, 1e-12)) - input)
    if reduction == "batchmean":
        return jnp.sum(out) / input.shape[0]
    return _reduce(out, reduction)


@wrap_op
def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean"):
    out = jnp.maximum(-label * (input - other) + margin, 0.0)
    return _reduce(out, reduction)


@wrap_op
def hinge_embedding_loss(input, label, margin=1.0, reduction="mean"):
    out = jnp.where(label == 1.0, input, jnp.maximum(margin - input, 0.0))
    return _reduce(out, reduction)


@wrap_op
def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean"):
    cos = (jnp.sum(input1 * input2, axis=-1)
           / jnp.maximum(jnp.linalg.norm(input1, axis=-1)
                         * jnp.linalg.norm(input2, axis=-1), 1e-12))
    out = jnp.where(label == 1, 1.0 - cos, jnp.maximum(cos - margin, 0.0))
    return _reduce(out, reduction)


@wrap_op
def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean"):
    def dist(a, b):
        return jnp.power(jnp.sum(jnp.power(jnp.abs(a - b) + epsilon, p),
                                 axis=-1), 1.0 / p)
    dp = dist(input, positive)
    dn = dist(input, negative)
    if swap:
        dn = jnp.minimum(dn, dist(positive, negative))
    out = jnp.maximum(dp - dn + margin, 0.0)
    return _reduce(out, reduction)


@wrap_op
def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum"):
    p = jax.nn.sigmoid(logit)
    ce = (1 - label) * logit + jnp.log1p(jnp.exp(-jnp.abs(logit))) \
        + jnp.maximum(-logit, 0.0)
    p_t = p * label + (1 - p) * (1 - label)
    loss = ce * ((1 - p_t) ** gamma)
    if alpha >= 0:
        alpha_t = alpha * label + (1 - alpha) * (1 - label)
        loss = alpha_t * loss
    if normalizer is not None:
        loss = loss / normalizer
    return _reduce(loss, reduction)


@wrap_op
def log_loss(input, label, epsilon=1e-4):
    return -(label * jnp.log(input + epsilon)
             + (1 - label) * jnp.log(1 - input + epsilon))


@wrap_op
def square_error_cost(input, label):
    return jnp.square(input - label)


@wrap_op
def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    # forward algorithm CTC in log space, vectorised over batch via vmap
    # log_probs: (T, B, C) paddle layout
    if log_probs.ndim == 3 and log_probs.shape[0] != labels.shape[0]:
        lp = jnp.transpose(log_probs, (1, 0, 2))  # (B, T, C)
    else:
        lp = log_probs
    lp = jax.nn.log_softmax(lp, axis=-1)
    B, T, C = lp.shape
    S = labels.shape[1]

    def single(lp_b, lab_b, t_len, l_len):
        ext = jnp.full((2 * S + 1,), blank, dtype=lab_b.dtype)
        ext = ext.at[1::2].set(lab_b)
        L = 2 * l_len + 1
        neg_inf = -1e30
        alpha = jnp.full((2 * S + 1,), neg_inf, jnp.float32)
        alpha = alpha.at[0].set(lp_b[0, blank])
        alpha = alpha.at[1].set(jnp.where(l_len > 0, lp_b[0, ext[1]], neg_inf))

        same_as_prev2 = jnp.concatenate(
            [jnp.array([True, True]), ext[2:] == ext[:-2]])

        def step(alpha, lp_t):
            a_prev = jnp.concatenate(
                [jnp.array([neg_inf], jnp.float32), alpha[:-1]])
            a_prev2 = jnp.concatenate(
                [jnp.array([neg_inf, neg_inf], jnp.float32), alpha[:-2]])
            a_prev2 = jnp.where(same_as_prev2, neg_inf, a_prev2)
            merged = jnp.logaddexp(jnp.logaddexp(alpha, a_prev), a_prev2)
            new_alpha = merged + lp_t[ext]
            return new_alpha, None

        def body(t, alpha):
            new_alpha, _ = step(alpha, lp_b[t])
            return jnp.where(t < t_len, new_alpha, alpha)

        alpha = jax.lax.fori_loop(1, T, body, alpha)
        final = jnp.logaddexp(alpha[2 * l_len], alpha[2 * l_len - 1])
        return -final

    losses = jax.vmap(single)(lp, labels, input_lengths, label_lengths)
    if reduction == "mean":
        return jnp.mean(losses / jnp.maximum(label_lengths, 1).astype(losses.dtype))
    return _reduce(losses, reduction)
