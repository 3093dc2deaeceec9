"""Plain reference for the ``nemotron_h`` family: forward pass, loss, nothing
else.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``
(on a TPU a float32 matmul otherwise runs in bf16 passes).  No kernels, no
grouped products, no sorting; imports nothing from ``paddle_tpu``.  It
follows ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` (``model_type:
nemotron_h``) as its ``config.json`` and the family's published modelling
code state it; the Mamba-2 mixer is Dao & Gu 2024.

Every block is one mixer alone: ``x <- x + mixer(rms_norm(x))``; the kind is
a character of the pattern string.

* ``M``, Mamba-2: ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC) + b)``,
  a causal depthwise convolution; ``x`` (H heads of P), ``B`` and ``C`` (G
  groups of N, head h reads group h // (H / G)); ``dt = softplus(dt +
  dt_bias)``; ``A = -exp(A_log)``; ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
  B_t``; ``y_t = C_t . S_t + D x_t``; ``out = (group_rms_norm(y * silu(z)) *
  w) W_out``.  The recurrence runs over chunks of the row carrying the
  state, and inside a chunk a token at a time would be too slow, so a chunk
  is computed in its quadratic form, every decay an explicit exponential of
  a difference of cumulative sums.
* ``*``, attention: ``[q | k | v] = u W_qkv`` (Hq heads, Hkv key/value heads
  of d; query head h reads key/value head h // (Hq / Hkv)), causal
  ``softmax(q k^T / sqrt(d)) v``, the scores of a block of query rows at
  a time against every key, then ``W_o``.  No positions.
* ``E``, experts: ``s = sigmoid(u W_r)`` over all the router's experts; the
  top k of ``s + bias``; weights ``s_i / (sum of chosen s + 1e-20) * scale``;
  expert ``relu(u W_up)^2 W_down``; output ``sum over i chosen AND held of
  w_i expert_i(u) + shared(u)``.  ``held`` is a list of expert ids: the
  weights hold those experts only, in that order, and what the others would
  add is left out.  Each held expert is computed densely for every token and
  masked: no sorting, no grouped product.

The weights are an ARGUMENT, a dict by the names the model gives them
(linear weights are stored ``(in, out)``, the convolution's ``(taps,
channels)``, tap j multiplying ``x_{t-(k-1)+j}``).  ``dtype`` is float32;
the controls that put this reference in the program's place run it with
bfloat16 throughout, or with bfloat16 everywhere but the router
(``router_dtype``), and a probe hands a block the experts another run chose
(``chosen``): ``tools/nemotron_h_controls.py``.

Departures from the source, all listed under ``assumed`` in the
configuration file: no rotary embedding (the published implementation
applies none; ``rope_theta`` is unused), the held share of the experts, the
sliced vocabulary.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
FIELDS = {
    MAMBA: ("norm.weight", "mixer.in_proj.weight", "mixer.conv1d_weight",
            "mixer.conv1d_bias", "mixer.A_log", "mixer.dt_bias", "mixer.D",
            "mixer.norm_weight", "mixer.out_proj.weight"),
    ATTENTION: ("norm.weight", "mixer.qkv_proj.weight",
                "mixer.o_proj.weight"),
    EXPERTS: ("norm.weight", "mixer.gate.weight",
              "mixer.gate.e_score_correction_bias",  # BIAS, below
              "mixer.experts.up_proj", "mixer.experts.down_proj",
              "mixer.shared_experts.up_proj.weight",
              "mixer.shared_experts.down_proj.weight"),
}


BIAS = "mixer.gate.e_score_correction_bias"


def layer_weights(weights: dict, i: int, kind: str) -> dict:
    """The tensors of block ``i``, by their field names.  The router's
    selection bias is a buffer, no parameter: a caller that holds the
    trainable tensors alone (the gradient comparison) leaves it out, and it
    is then what it is at the seed, zero."""
    prefix = "backbone.layers.%d." % i
    out = {f: weights[prefix + f] for f in FIELDS[kind]
           if f != BIAS or prefix + f in weights}
    if kind == EXPERTS and BIAS not in out:
        out[BIAS] = jnp.zeros(out["mixer.gate.weight"].shape[-1:], F32)
    return out


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * weight


def embed(table, ids, dtype=F32):
    return jnp.asarray(table).astype(dtype)[ids]


# -- Mamba-2 --------------------------------------------------------------------

def causal_depthwise_conv(x, taps, bias):
    """x (b, s, c), taps (k, c): ``y_t = sum_j taps[j] x_{t-(k-1)+j} + b``."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, j:j + s] for j in range(k)) + bias


def ssm_scan(x, dt, a, b, c, chunk, carry_state=True):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = C_t . S_t``
    over x (b, s, H, P), dt (b, s, H), a (H,), b and c (b, s, H, N) (already
    one a head), by a ``lax.scan`` over chunks of ``chunk`` tokens that
    carries the state (b, H, P, N).  ``carry_state=False`` is the broken
    control: every chunk starts from a zero state."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    pad = -s % chunk
    if pad:     # steps of size zero: no decay, no input
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                               (t.ndim - 2)) for t in (x, dt, b, c))
    chunks = lambda t: jnp.moveaxis(
        t.reshape((bsz, -1, chunk) + t.shape[2:]), 1, 0)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one_chunk(state, inputs):
        xc, dtc, bc, cc = inputs                  # (b, L, H, ...)
        cum = jnp.cumsum(dtc * a, axis=1)         # (b, L, H), inclusive
        # from the state that entered: decayed to position l
        y = jnp.einsum("blhn,bhpn->blhp", cc, state) * jnp.exp(cum)[..., None]
        # from the chunk's own tokens s <= l
        decay = jnp.exp(jnp.where(
            causal[None, :, :, None],
            cum[:, :, None, :] - cum[:, None, :, :], -jnp.inf))  # (b,l,s,H)
        scores = jnp.einsum("blhn,bshn->blsh", cc, bc) * decay \
            * dtc[:, None, :, :]
        y = y + jnp.einsum("blsh,bshp->blhp", scores, xc)
        to_end = jnp.exp(cum[:, -1:] - cum) * dtc               # (b, L, H)
        new = (jnp.exp(cum[:, -1])[..., None, None] * state
               + jnp.einsum("bshp,bsh,bshn->bhpn", xc, to_end, bc))
        return (new if carry_state else state), y

    # under jax.grad a chunk keeps its inputs and the state that entered,
    # and makes its (L, L) arrays again
    _, ys = jax.lax.scan(jax.checkpoint(one_chunk),
                         jnp.zeros((bsz, h, p, n), x.dtype),
                         tuple(chunks(t) for t in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1).reshape(bsz, s + pad, h, p)[:, :s]


def mamba2_mixer(u, w: dict, heads, head_dim, groups, state, chunk, eps,
                 carry_state=True):
    bsz, s, _ = u.shape
    d_inner, gn = heads * head_dim, groups * state
    proj = u @ w["mixer.in_proj.weight"]
    z, xbc, dt = (proj[..., :d_inner], proj[..., d_inner:2 * d_inner + 2 * gn],
                  proj[..., 2 * d_inner + 2 * gn:])
    xbc = jax.nn.silu(causal_depthwise_conv(
        xbc, w["mixer.conv1d_weight"], w["mixer.conv1d_bias"]))
    x = xbc[..., :d_inner].reshape(bsz, s, heads, head_dim)
    per_head = lambda t: jnp.repeat(
        t.reshape(bsz, s, groups, state), heads // groups, axis=2)
    b, c = (per_head(xbc[..., d_inner:d_inner + gn]),
            per_head(xbc[..., d_inner + gn:]))
    dt = jax.nn.softplus(dt + w["mixer.dt_bias"])
    y = ssm_scan(x, dt, -jnp.exp(w["mixer.A_log"]), b, c, chunk, carry_state)
    y = y + w["mixer.D"][:, None] * x
    gated = (y.reshape(bsz, s, d_inner) * jax.nn.silu(z)).reshape(
        bsz, s, groups, d_inner // groups)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + eps)
    return (normed.reshape(bsz, s, d_inner) * w["mixer.norm_weight"]) \
        @ w["mixer.out_proj.weight"]


# -- attention ------------------------------------------------------------------

def attention(u, w: dict, heads, kv_heads, head_dim, block=256):
    """Causal grouped-query attention; the scores of ``block`` query rows
    at a time against every key, so that no (s, s) array exists."""
    bsz, s, _ = u.shape
    qkv = u @ w["mixer.qkv_proj.weight"]
    q = qkv[..., :heads * head_dim].reshape(bsz, s, heads, head_dim)
    kv = qkv[..., heads * head_dim:].reshape(bsz, s, 2, kv_heads, head_dim)
    k, v = (jnp.repeat(kv[:, :, i], heads // kv_heads, axis=2)
            for i in range(2))
    block = min(block, s)
    pad = -s % block
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    starts = jnp.arange(0, s + pad, block)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, block, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(head_dim)
        visible = (jnp.arange(s)[None, :]
                   <= (start + jnp.arange(block))[:, None])
        probs = jax.nn.softmax(
            jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    # under jax.grad a block's scores are made again, never kept
    out = jax.lax.map(jax.checkpoint(rows), starts)  # (blocks,b,block,H,d)
    out = jnp.moveaxis(out, 0, 1).reshape(bsz, s + pad, heads * head_dim)
    return out[:, :s] @ w["mixer.o_proj.weight"]


# -- experts --------------------------------------------------------------------

def relu2_mlp(u, up, down):
    return jnp.square(jax.nn.relu(u @ up)) @ down


def route(u, w: dict, top_k, scale, chosen=None):
    """(chosen (b, s, k) expert ids, weights (b, s, k) in ``u``'s type).
    The scores are computed in the type of the router's weight: float32
    for the reference, bfloat16 for the control without a float32 router.
    With ``chosen`` given the choice is taken from there (a probe: what
    another run chose) and the weights are this run's scores of it."""
    router = w["mixer.gate.weight"]
    scores = jax.nn.sigmoid(u.astype(router.dtype) @ router)
    if chosen is None:
        _, chosen = jax.lax.top_k(scores + w[BIAS], top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                        + 1e-20) * scale
    return chosen, weights.astype(u.dtype)


def experts(u, w: dict, held, top_k, scale, with_shared=True, chosen=None):
    """The held experts' part of the layer plus the shared expert.
    ``held[j]`` is the router's id of the expert whose weights are
    ``up_proj[j]`` and ``down_proj[j]``."""
    chosen, weights = route(u, w, top_k, scale, chosen)
    out = jnp.zeros_like(u)
    for j, expert_id in enumerate(held):
        gate = jnp.sum(jnp.where(chosen == expert_id, weights, 0.0), axis=-1)
        out = out + gate[..., None] * relu2_mlp(
            u, w["mixer.experts.up_proj"][j], w["mixer.experts.down_proj"][j])
    if with_shared:
        out = out + relu2_mlp(u, w["mixer.shared_experts.up_proj.weight"],
                              w["mixer.shared_experts.down_proj.weight"])
    return out


# -- the model ------------------------------------------------------------------

ROUTER = ("mixer.gate.weight", BIAS)


def _typed(w: dict, dtype, router_dtype) -> dict:
    return {k: jnp.asarray(v).astype(
        router_dtype if k in ROUTER and router_dtype else dtype)
        for k, v in w.items()}


def choice(x, w: dict, model: dict, dtype=F32, router_dtype=None):
    """The experts an ``E`` block of these types chooses for ``x``: (b, s,
    k) ids, as :func:`block` computes them."""
    with jax.default_matmul_precision("highest"):
        w = _typed(w, dtype, router_dtype)
        u = rms_norm(x, w["norm.weight"], model["layer_norm_epsilon"])
        return route(u, w, model["num_experts_per_tok"],
                     model["routed_scaling_factor"])[0]


def block(x, w: dict, kind: str, model: dict, dtype=F32, with_experts=True,
          carry_state=True, router_dtype=None, chosen=None):
    """One block on activations (b, s, h) of ``dtype``.  ``model`` holds the
    sizes by the configuration's names.  The two flags are the controls: a
    reference without its expert layer, or whose scan forgets its state
    between chunks, must read not correct.  ``router_dtype`` (default
    ``dtype``) is the type of the router's weight, bias and scores;
    ``chosen`` as :func:`route` takes it."""
    with jax.default_matmul_precision("highest"):
        w = _typed(w, dtype, router_dtype)
        eps = model["layer_norm_epsilon"]
        u = rms_norm(x, w["norm.weight"], eps)
        if kind == MAMBA:
            out = mamba2_mixer(
                u, w, model["mamba_num_heads"], model["mamba_head_dim"],
                model["n_groups"], model["ssm_state_size"],
                model["chunk_size"], eps, carry_state)
        elif kind == ATTENTION:
            out = attention(u, w, model["num_attention_heads"],
                            model["num_key_value_heads"], model["head_dim"])
        elif not with_experts:
            return x
        else:
            out = experts(u, w, model["held_experts"],
                          model["num_experts_per_tok"],
                          model["routed_scaling_factor"], chosen=chosen)
        return x + out.astype(dtype)


def head(x, norm_w, head_w, eps, dtype=F32):
    """Final RMSNorm and the untied head: (b, s, h) -> (b, s, V) float32."""
    with jax.default_matmul_precision("highest"):
        return (rms_norm(x, jnp.asarray(norm_w).astype(dtype), eps)
                @ jnp.asarray(head_w).astype(dtype)).astype(F32)


def forward(weights: dict, ids, model: dict, dtype=F32, remat=False, **flags):
    """Logits (b, s, V) in float32 for token ids (b, s).  ``remat`` wraps a
    block in ``jax.checkpoint``: under ``jax.grad`` what is kept is then the
    blocks' inputs and one block's activations, not every block's."""
    x = embed(weights["backbone.embeddings.weight"], ids, dtype)
    for i, kind in enumerate(model["hybrid_override_pattern"]):
        run = functools.partial(block, kind=kind, model=model, dtype=dtype,
                                **flags)
        if remat:
            run = jax.checkpoint(run)
        x = run(x, layer_weights(weights, i, kind))
    return head(x, weights["backbone.norm_f.weight"],
                weights["lm_head.weight"], model["layer_norm_epsilon"], dtype)


def token_losses(logits, ids):
    """Next-token cross-entropy at positions 0..s-2 of each row: (b, s-1)."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(F32), axis=-1)
    return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]


def loss(weights: dict, ids, model: dict, dtype=F32, **flags):
    """Mean next-token cross-entropy (every position but the last predicts
    its successor), recomputed a block at a time under ``jax.grad``."""
    return jnp.mean(token_losses(
        forward(weights, ids, model, dtype, remat=True, **flags), ids))
