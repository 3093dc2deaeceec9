"""The comparisons that decide ``correct``.

Every comparison is between the system and ``benchmarks/reference/gpt_ref.py``
on weights and inputs that are functions of ``--seed`` on both sides, or a
test of finiteness.  Nothing rests on a loss falling, on a value near ln V or
on two streams being equal.  All of it runs after the window, outside every
timed interval and outside ``setup_s``; each check compiles at one padded
width, so it compiles once.

Tolerances and their measured basis (PR 24; the chip is one TPU v5e, the
system computes in bf16, the reference in float32):

* ``LOGITS_RMS_TOL``: root-mean-square difference of the logits over the
  standard deviation of the reference's logits (``BASIS`` has what was
  seen: 0.012 on the chip, 0.075 and more under fp8 weights).
* ``LOSS_REL_TOL``: relative difference of the first timed step's loss.
* ``DEFICIT_TOL``: how far below the reference's best logit a served token
  may lie, as a share of the spread between the best logit and the row mean
  (``chip_smoke.py::check_against_forward``'s rule and margin).
* ``GRAD_REL_TOL``: per-tensor relative gradient error, worst tensor
  (traced run only).

The fp8 and dropped-residual mutations that show each tolerance
discriminates run in ``tests/benchmarks`` at tiny size on the CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import gpt_ref

#: tolerance = widest value seen x margin; the measured basis is kept here
BASIS = {
    "logits_rel_rms": {
        "cpu_tiny_bf16_widest_of_5_seeds": 0.0053,
        "chip_train_widest_of_6_seeds": 0.01216,      # gpt2-medium, PR 24
        "chip_serve_c13b_one_seed": 0.01165,          # PR 24, one traced run
        "cpu_tiny_fp8_weights_least_of_5_seeds": 0.0752,
        "margin": "0.035 is 2.9 x the widest seen on the chip and under "
                  "half of the least fp8 reading; a dropped residual "
                  "reads above 0.07 too (tests/benchmarks)",
    },
    "loss_rel": {
        "chip_widest_of_6_seeds": 8.0e-6,
        "note": "weak at random initialisation (the loss sits near ln V "
                "whatever the blocks compute: PR 23 read 3.4e-5 under fp8 "
                "and 3.6e-4 with a dropped residual); kept as a guard on "
                "the loss path, the logits comparison discriminates",
    },
    "deficit": {
        "chip_serve_c13b_one_seed": 0.0056,           # 1,086 tokens, PR 24
        "chip_smoke_margin": 0.1,
        "random_tokens_cpu_tiny": "above 0.3 (tests/benchmarks)",
    },
    "grad_rel": {
        "chip_widest_of_2_seeds": 0.0156,             # PR 24, traced runs
        "pr23_chip_widest_of_3_seeds": 0.0147,
        "pr23_fp8": 0.335, "pr23_dropped_residual": 1.63,
    },
}
LOGITS_RMS_TOL = 0.035
LOSS_REL_TOL = 2e-3
DEFICIT_TOL = 0.1
GRAD_REL_TOL = 0.05


def reference_forward_fn(num_layers: int, num_heads: int, eps: float):
    """``(weights, ids) -> float32 logits``, the reference run one block at
    a time: one small program for the block, called ``num_layers`` times,
    with the weights as arguments."""
    embed = jax.jit(gpt_ref.embed)
    block = jax.jit(functools.partial(gpt_ref.block, num_heads=num_heads,
                                      eps=eps))
    head = jax.jit(functools.partial(gpt_ref.head, eps=eps))

    def forward(weights, ids):
        x = embed(weights["gpt.wte.weight"], weights["gpt.wpe.weight"], ids)
        for i in range(num_layers):
            x = block(x, gpt_ref.layer_weights(weights, i))
        return head(x, weights["gpt.ln_f.weight"], weights["gpt.ln_f.bias"],
                    weights["gpt.wte.weight"])
    return forward


def system_forward_fn(model):
    """``(state, ids) -> float32 logits`` through the model's own forward
    in eval mode.  The weights are an argument: closed over, they would be
    compiled in as constants (``chip_smoke.py::reference_logits_fn``)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import functional_call

    @jax.jit
    def forward(state, ids):
        model.eval()
        out, _ = functional_call(model, state, paddle.Tensor(ids))
        return out.astype(jnp.float32)
    return forward


@jax.jit
def _logits_errors(sys_logits, ref_logits):
    diff = sys_logits - ref_logits
    scale = jnp.std(ref_logits)
    return (jnp.sqrt(jnp.mean(jnp.square(diff))) / scale,
            jnp.max(jnp.abs(diff)) / scale,
            jnp.all(jnp.isfinite(sys_logits)))


def logits_errors(sys_logits, ref_logits) -> dict:
    rms, worst, finite = _logits_errors(sys_logits, ref_logits)
    return {"rel_rms": float(rms), "rel_max": float(worst),
            "finite": bool(finite)}


@jax.jit
def _mean_loss(logits, ids):
    return jnp.mean(gpt_ref.token_losses(logits, ids))


def reference_loss(ref_forward, weights, batch, rows_per_call: int = 2):
    """The reference's loss on ``batch`` (b, s), a few rows at a time so
    that the float32 logits fit beside the system's state."""
    b = int(batch.shape[0])
    parts = [_mean_loss(ref_forward(weights, batch[i:i + rows_per_call]),
                        batch[i:i + rows_per_call])
             for i in range(0, b, rows_per_call)]
    if b % rows_per_call:
        raise ValueError("batch %d is not a multiple of %d"
                         % (b, rows_per_call))
    return float(sum(float(p) for p in parts) / len(parts))


@jax.jit
def _deficit(logits, first, count, chosen):
    """Worst teacher-forced deficit over positions first..first+count-1 of
    one padded row of logits (W, V); ``chosen[j]`` is the token served at
    position first + j."""
    width = logits.shape[0]
    pos = jnp.arange(width)
    rows = (pos >= first) & (pos < first + count)
    tok = jnp.take(chosen, jnp.clip(pos - first, 0, chosen.shape[0] - 1))
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
    deficit = (best - got) / (best - jnp.mean(logits, axis=-1))
    return jnp.max(jnp.where(rows, deficit, 0.0))


def served_deficit(ref_forward, weights, requests, width: int) -> float:
    """Worst deficit over ``requests``, each ``(prompt ids, served ids)``:
    at every served position, how far the served token's reference logit
    lies below the reference's best, over the spread between the best and
    the row mean.  An argmax comparison would flake: with random weights
    the top two of 50k logits sit about a bf16 rounding apart."""
    worst = 0.0
    for prompt, stream in requests:
        ids = list(prompt) + list(stream)
        if len(ids) > width:
            raise ValueError("a stream of %d tokens exceeds the check's "
                             "width %d" % (len(ids), width))
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(ids)] = ids
        chosen = np.zeros((width,), np.int32)
        chosen[:len(stream)] = stream
        logits = ref_forward(weights, jnp.asarray(padded))[0]
        worst = max(worst, float(_deficit(
            logits, len(prompt) - 1, len(stream), jnp.asarray(chosen))))
    return worst


def grad_errors(sys_grads: dict, ref_grads: dict) -> dict:
    """Per-tensor relative error ``|g_sys - g_ref| / |g_ref|`` (Frobenius),
    computed on the device; one scalar per tensor comes to the host."""
    @jax.jit
    def rel(a, b):
        a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.linalg.norm((a32 - b32).ravel()) / jnp.maximum(
            jnp.linalg.norm(b32.ravel()), 1e-30)
    errs = {k: float(rel(sys_grads[k], ref_grads[k])) for k in ref_grads}
    worst = max(errs, key=errs.get)
    return {"worst": errs[worst], "tensor": worst,
            "median": float(np.median(list(errs.values())))}
