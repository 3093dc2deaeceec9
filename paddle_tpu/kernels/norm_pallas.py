"""Pallas TPU LayerNorm + row-softmax kernels (SURVEY.md §7 stage 3 hot set;
reference CUDA: paddle/phi/kernels/gpu/layer_norm_kernel.cu,
fused_layernorm_residual_dropout_bias.h; softmax_kernel.cu).

Design: rows (all leading dims flattened) are tiled over a 1-D grid; each
grid step loads a (BLOCK_ROWS, F) tile into VMEM, computes f32 statistics on
the VPU, and writes the normalized tile back in the input dtype.  The
backward kernels recompute x_hat from the saved (mean, rstd) row statistics
— O(F) memory per row, matching the fused CUDA kernels' design.

NOTE on dispatch: XLA already fuses layer-norm/softmax chains to ~peak on
TPU (measured — PERF.md), so the framework defaults to the XLA path; these
kernels are selected via FLAGS_use_pallas_norm=1 and exist as the
hand-kernel escape hatch (and the pattern template for custom fusions via
utils.cpp_extension.register_op).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.dtype import x64_scope

DEFAULT_BLOCK_ROWS = 256


def _shrink_rows(block_rows, n):
    """The hand-tuned row-block policy: shrink the default to the largest
    power-of-two divisor of n (floor 8)."""
    br = min(block_rows, n)
    while br > 8 and n % br:
        br //= 2
    return br


def autotune_key(n, f, dtype):
    from . import autotune as at
    return {"n": int(n), "f": int(f), "dtype": str(jnp.dtype(dtype)),
            "platform": at.platform()}


def _ln_candidates(key):
    """ln autotune family: the row-block size of the LayerNorm grid.
    Candidate [0] is the hand-tuned _shrink_rows default."""
    n = key["n"]
    br0 = _shrink_rows(DEFAULT_BLOCK_ROWS, n)
    cands = [{"variant": "base", "config": {"block_rows": br0}}]
    for br in (1024, 512, 256, 128, 64, 32, 16, 8):
        if br != br0 and br <= n and n % br == 0:
            cands.append({"variant": "base", "config": {"block_rows": br}})
    return cands


#: per-key synthetic operands shared across one tune() run's candidates
#: (see ce_pallas._LSE_RUNNER_DATA); freed by the cleanup hook
_LN_RUNNER_DATA: dict = {}


def _ln_runner(cand, key):
    import numpy as np
    from . import autotune as at
    n, f = key["n"], key["f"]
    dtype = jnp.dtype(key["dtype"])
    interpret = key["platform"] != "tpu"
    br = cand["config"]["block_rows"]
    ks = at.key_str(key)
    data = _LN_RUNNER_DATA.get(ks)
    if data is None:
        rng = np.random.RandomState(0)
        data = (jnp.asarray(rng.standard_normal((n, f)), dtype),
                jnp.ones((f,), dtype), jnp.zeros((f,), dtype))
        _LN_RUNNER_DATA[ks] = data
    x2, gamma, beta = data

    def timed(x, g, b):
        # same x64-off trace scope as the production entry (_ln_core)
        with x64_scope(False):
            return _ln_fwd(x, g, b, 1e-5, br, interpret)
    fn = jax.jit(timed)

    def run():
        jax.block_until_ready(fn(x2, gamma, beta))
    return run


def _ln_runner_cleanup(key):
    from . import autotune as at
    _LN_RUNNER_DATA.pop(at.key_str(key), None)


def _ln_resolve_rows(n, f, dtype, block_rows):
    """Row-block pick for one call: explicit non-default block_rows is
    honored as-is; the default resolves through the autotuner (returning
    the hand-tuned shrink unless a tuned/pinned config exists)."""
    if block_rows != DEFAULT_BLOCK_ROWS:
        return _shrink_rows(block_rows, n)
    from . import autotune as at
    cand = at.resolve("ln", autotune_key(n, f, dtype))
    br = cand.get("config", {}).get("block_rows")
    if isinstance(br, int) and 8 <= br <= n and n % br == 0:
        return br
    return _shrink_rows(block_rows, n)


def _supported_feature_dim(f: int) -> bool:
    return f % 128 == 0


# ---------------------------------------------------------------------------
# LayerNorm
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, g_ref, b_ref, o_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)              # (R, F)
    mean = jnp.mean(x, axis=-1)
    var = jnp.mean(jnp.square(x), axis=-1) - jnp.square(mean)
    rstd = jax.lax.rsqrt(var + jnp.float32(eps))
    xhat = (x - mean[:, None]) * rstd[:, None]
    o_ref[...] = (xhat * g_ref[...].astype(jnp.float32)[None, :] +
                  b_ref[...].astype(jnp.float32)[None, :]).astype(o_ref.dtype)
    # (R, 1) layout: a bare (R,) f32 output tiles T(256) in Mosaic vs XLA's
    # T(512) and fails layout verification on real TPUs
    mean_ref[...] = mean[:, None]
    rstd_ref[...] = rstd[:, None]


def _ln_bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, do_ref,
                   dx_ref, dg_ref, db_ref):
    i = jax.lax.convert_element_type(pl.program_id(0), jnp.int32)
    x = x_ref[...].astype(jnp.float32)              # (R, F)
    do = do_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)[None, :]
    mean = mean_ref[...]            # (R, 1)
    rstd = rstd_ref[...]
    xhat = (x - mean) * rstd
    dxhat = do * g
    # dx = rstd * (dxhat - mean(dxhat) - xhat * mean(dxhat * xhat))
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (dxhat - m1 - xhat * m2)).astype(dx_ref.dtype)
    # parameter grads accumulate across the sequential row-block grid
    @pl.when(i == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
    dg_ref[...] = dg_ref[...] + jnp.sum(do * xhat, axis=0)
    db_ref[...] = db_ref[...] + jnp.sum(do, axis=0)


def _ln_fwd(x2, gamma, beta, eps, block_rows, interpret):
    n, f = x2.shape
    nb = n // block_rows
    out, mean, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
            pl.BlockSpec((f,), lambda i: (0,)),
            pl.BlockSpec((f,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, f), x2.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, gamma, beta)
    return out, mean, rstd


def _ln_bwd(x2, gamma, mean, rstd, do2, block_rows, interpret):
    n, f = x2.shape
    nb = n // block_rows
    dx, dg, db = pl.pallas_call(
        _ln_bwd_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
            pl.BlockSpec((f,), lambda i: (0,)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
            pl.BlockSpec((f,), lambda i: (0,)),       # revisited accumulator
            pl.BlockSpec((f,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, f), x2.dtype),
            jax.ShapeDtypeStruct((f,), jnp.float32),
            jax.ShapeDtypeStruct((f,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(x2, gamma, mean, rstd, do2)
    return dx, dg, db


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def layer_norm_pallas(x, gamma, beta, eps=1e-5,
                      block_rows=DEFAULT_BLOCK_ROWS, interpret=False):
    """LayerNorm over the last dim.  x: (..., F); gamma/beta: (F,).
    Requires F % 128 == 0 and rows % block_rows == 0 (supported() gates)."""
    out, _, _ = _ln_core(x, gamma, beta, eps, block_rows, interpret)
    return out


def _ln_core(x, gamma, beta, eps, block_rows, interpret):
    f = x.shape[-1]
    x2 = x.reshape(-1, f)
    n = x2.shape[0]
    br = _ln_resolve_rows(n, f, x.dtype, block_rows)
    if n % br or not _supported_feature_dim(f):
        raise ValueError(
            f"layer_norm_pallas: shape ({n}, {f}) not tileable "
            f"(rows %% {br}, feature %% 128)")
    with x64_scope(False):
        out, mean, rstd = _ln_fwd(x2, gamma, beta, eps, br, interpret)
    return out.reshape(x.shape), mean, rstd


def _ln_vjp_fwd(x, gamma, beta, eps, block_rows, interpret):
    out, mean, rstd = _ln_core(x, gamma, beta, eps, block_rows, interpret)
    return out, (x, gamma, mean, rstd)


def _ln_vjp_bwd(eps, block_rows, interpret, res, g):
    x, gamma, mean, rstd = res
    f = x.shape[-1]
    x2 = x.reshape(-1, f)
    n = x2.shape[0]
    # same deterministic pick as the forward (memoised, so fwd/bwd agree)
    br = _ln_resolve_rows(n, f, x.dtype, block_rows)
    with x64_scope(False):
        dx, dg, db = _ln_bwd(x2, gamma, mean, rstd, g.reshape(-1, f), br,
                             interpret)
    return (dx.reshape(x.shape), dg.astype(gamma.dtype),
            db.astype(gamma.dtype))


layer_norm_pallas.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


# ---------------------------------------------------------------------------
# row softmax
# ---------------------------------------------------------------------------

def _softmax_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


def softmax_pallas(x, block_rows=DEFAULT_BLOCK_ROWS, interpret=False):
    """Numerically-stable softmax over the last dim (f32 statistics).
    Differentiable via jax's autodiff over the kernel's XLA recompute is NOT
    provided — use for inference paths; training softmax lives inside the
    flash-attention kernels."""
    f = x.shape[-1]
    x2 = x.reshape(-1, f)
    n = x2.shape[0]
    br = min(block_rows, n)
    while br > 8 and n % br:
        br //= 2
    if n % br or not _supported_feature_dim(f):
        raise ValueError(
            f"softmax_pallas: shape ({n}, {f}) not tileable")
    with x64_scope(False):
        out = pl.pallas_call(
            _softmax_kernel,
            grid=(n // br,),
            in_specs=[pl.BlockSpec((br, f), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((br, f), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, f), x.dtype),
            interpret=interpret,
        )(x2)
    return out.reshape(x.shape)


def _ln_traceable(cand, key, interpret=True):
    """Data-free candidate program for the TPU504 VMEM estimator and the
    trace-tier audit (see flash_attention_pallas._fwd_traceable)."""
    n, f = key["n"], key["f"]
    dtype = jnp.dtype(key["dtype"])
    br = cand["config"]["block_rows"]

    def fn(x, g, b):
        with x64_scope(False):
            return _ln_fwd(x, g, b, 1e-5, br, interpret)
    sds = jax.ShapeDtypeStruct
    return fn, (sds((n, f), dtype), sds((f,), dtype), sds((f,), dtype))


def _ln_register():
    from . import autotune as at
    at.register_family("ln", _ln_candidates, _ln_runner,
                       cleanup=_ln_runner_cleanup, traceable=_ln_traceable)


_ln_register()
