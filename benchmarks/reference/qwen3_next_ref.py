"""Plain reference for the ``qwen3_next`` family: forward pass, loss, nothing
else.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``
(on a TPU a float32 matmul otherwise runs in bf16 passes).  No kernels, no
chunked algebra, no grouped products, no sorting; imports nothing from
``paddle_tpu``.  It follows ``Qwen/Qwen3-Next-80B-A3B-Instruct`` (``model_type:
qwen3_next``) as its ``config.json`` and the family's published modelling code
state it; the recurrent layer is Yang, Kautz & Hatamizadeh 2024, "Gated Delta
Networks" (arXiv:2412.06464).

A layer is ``x <- x + mixer(N(x))`` then ``x <- x + experts(N(x))``, ``N(x) =
x / sqrt(mean x^2 + eps) * (1 + w)`` (a zero-centred gain); the kind of mixer
is an entry of ``layer_types``.

* ``linear_attention``, Gated DeltaNet: ``[q | k | v | z] = u W_qkvz``, ``[b |
  a] = u W_ba``; ``[q | k | v] = silu(conv([q | k | v]))``, a causal depthwise
  convolution without bias; ``beta = sigmoid(b)``, ``g = -exp(A_log)
  softplus(a + dt_bias)`` a value head; q and k L2-normalised a head, ``q <-
  q / sqrt(d_k)``, key head j serving value heads ``j R .. j R + R - 1``.  A
  value head's state ``S`` (d_k, d_v): ``S <- e^{g_t} S``; ``u_t = beta_t (v_t
  - S^T k_t)``; ``S <- S + k_t u_t^T``; ``o_t = S^T q_t``.  Output ``(rms(o)
  * w_norm * silu(z)) W_out``, the norm over a head's lanes with a plain
  gain.  The recurrence runs A TOKEN AT A TIME (``lax.scan``), in blocks of
  the row that carry the state (under ``jax.grad`` a block keeps its inputs
  and the state that entered): nothing of the chunked algebra the program
  uses.
* ``full_attention``: ``[q | gate] = u W_q`` a head, ``k = u W_k``, ``v = u
  W_v`` (Hq query heads over Hkv key/value heads of d; query head h reads
  key/value head ``h // (Hq / Hkv)``); ``q <- N_head(q)``, ``k <- N_head(k)``
  (zero-centred gains over the d lanes); rotary on lanes ``0 .. rotary_dim -
  1`` of q and k, half-split pairing, ``theta``; causal ``softmax(q k^T /
  sqrt(d)) v`` a block of query rows at a time over blocks of keys with a
  running maximum and sum, so that no (s, s) array exists; ``(attn *
  sigmoid(gate)) W_o``.
* experts: ``p = softmax(u W_r)`` over all the router's experts; the top k;
  weights ``p_i / (sum of the chosen p)``; expert ``(silu(u W_gate) * (u
  W_up)) W_down``; output ``sum over i chosen AND held of w_i expert_i(u) +
  sigmoid(u . w_sg) shared(u)``.  ``held`` is a list of expert ids: the
  weights hold those experts only, in that order, and what the others would
  add is left out.  Each held expert is computed densely for every token
  and masked, one after the other (a ``lax.scan`` over the list of held
  experts, each a ``jax.checkpoint``): no sorting, no grouped product.

The weights are an ARGUMENT, a dict by the names the model gives them
(linear weights are stored ``(in, out)``, the convolution's ``(taps,
channels)``, tap j multiplying ``x_{t-(k-1)+j}``).  ``dtype`` is float32;
the controls that put this reference in the program's place run it with
bfloat16 throughout, or with bfloat16 everywhere but the router
(``router_dtype``), and a probe hands a layer the experts another run chose
(``chosen``): ``tools/qwen3_next_controls.py``.

Departures from the source, all listed under ``assumed`` in the
configuration file: the fused projections' column order (``[q | k | v | z]``
and ``[b | a]`` flat, heads in order, where the published weights interleave
them a key head; ``[q | gate]`` a head as published), the held share of the
experts, the sliced vocabulary, no multi-token-prediction layer (the source's
``config`` has no key for it).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"
MIXER = {
    LINEAR: ("linear_attn.in_proj_qkvz.weight", "linear_attn.in_proj_ba.weight",
             "linear_attn.conv1d_weight", "linear_attn.A_log",
             "linear_attn.dt_bias", "linear_attn.norm_weight",
             "linear_attn.out_proj.weight"),
    FULL: ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
           "self_attn.v_proj.weight", "self_attn.q_norm.weight",
           "self_attn.k_norm.weight", "self_attn.o_proj.weight"),
}
EXPERTS = ("mlp.gate.weight", "mlp.experts.gate_proj", "mlp.experts.up_proj",
           "mlp.experts.down_proj", "mlp.shared_experts.gate_proj.weight",
           "mlp.shared_experts.up_proj.weight",
           "mlp.shared_experts.down_proj.weight", "mlp.shared_gate")
NORMS = ("input_layernorm.weight", "post_attention_layernorm.weight")
ROUTER = ("mlp.gate.weight",)


def layer_types(model: dict) -> tuple:
    """The kind of mixer of every layer as run: ``layer_types`` where the
    model dict gives it, else full attention at every
    ``full_attention_interval``-th layer."""
    if model.get("layer_types"):
        return tuple(model["layer_types"])
    every = model["full_attention_interval"]
    return tuple(FULL if (i + 1) % every == 0 else LINEAR
                 for i in range(model["num_hidden_layers"]))


def layer_weights(weights: dict, i: int, kind: str) -> dict:
    """The tensors of layer ``i``, by their field names."""
    prefix = "model.layers.%d." % i
    return {f: weights[prefix + f] for f in NORMS + MIXER[kind] + EXPERTS}


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1,
                                      keepdims=True) + eps) * gain


def embed(table, ids, dtype=F32):
    return jnp.asarray(table).astype(dtype)[ids]


# -- Gated DeltaNet -------------------------------------------------------------

def causal_depthwise_conv(x, taps):
    """x (b, s, c), taps (k, c): ``y_t = sum_j taps[j] x_{t-(k-1)+j}``."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, j:j + s] for j in range(k))


def l2_normalize(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1,
                                     keepdims=True) + eps)


def delta_rule(q, k, v, g, beta, block=128, carry_state=True,
               correction=True):
    """The gated delta rule a token at a time over q, k (b, s, H, d_k)
    (already one a value head), v (b, s, H, d_v), g and beta (b, s, H), by
    a ``lax.scan`` over blocks of ``block`` tokens that carries the state
    (b, H, d_k, d_v) and inside a block a ``lax.scan`` over its tokens.
    The two flags are the broken controls: ``carry_state=False`` starts
    every block from a zero state, ``correction=False`` drops ``S^T k_t``
    (``u_t = beta_t v_t``: plain gated linear attention)."""
    bsz, s, h, dk = k.shape
    dv = v.shape[-1]
    block = min(block, s)
    pad = -s % block
    if pad:     # tokens that neither decay (g = 0) nor write (beta = 0)
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (t.ndim - 2)) for t in (q, k, v, g, beta))
    # (blocks, tokens of a block, b, H, ...)
    blocks = lambda t: jnp.moveaxis(
        t.reshape((bsz, -1, block) + t.shape[2:]), (1, 2), (0, 1))

    def token(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs       # (b, H, ...)
        state = jnp.exp(g_t)[..., None, None] * state
        seen = jnp.sum(state * k_t[..., :, None], axis=-2) if correction \
            else 0.0
        u_t = beta_t[..., None] * (v_t - seen)
        state = state + k_t[..., :, None] * u_t[..., None, :]
        return state, jnp.sum(state * q_t[..., :, None], axis=-2)

    def one_block(state, inputs):
        new, out = jax.lax.scan(token, state, inputs)
        return (new if carry_state else state), out

    # under jax.grad a block keeps its inputs and the state that entered,
    # and makes its tokens' states again
    _, out = jax.lax.scan(jax.checkpoint(one_block),
                          jnp.zeros((bsz, h, dk, dv), q.dtype),
                          tuple(blocks(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(out, (0, 1), (1, 2)).reshape(
        bsz, s + pad, h, dv)[:, :s]


def gated_delta_net(u, w: dict, key_heads, value_heads, dk, dv, eps,
                    carry_state=True, correction=True, head_groups=4):
    """The mixer.  Heads do not mix between the two projections, so
    everything between them runs for ``head_groups`` groups of key heads
    (with their value heads) one after the other (``lax.map``, a group a
    ``jax.checkpoint``): at 16k tokens a group's float32 arrays are a
    quarter of the layer's."""
    bsz, s, _ = u.shape
    key_dim, value_dim = key_heads * dk, value_heads * dv
    rep = value_heads // key_heads
    groups = math.gcd(head_groups, key_heads)
    hk, hv = key_heads // groups, value_heads // groups
    qkvz = u @ w["linear_attn.in_proj_qkvz.weight"]
    ba = u @ w["linear_attn.in_proj_ba.weight"]
    taps = w["linear_attn.conv1d_weight"]
    # a group's slice of each part of [q | k | v | z], of [b | a], of the
    # taps and of the per-head vectors, the group leading
    cuts = (0, key_dim, 2 * key_dim, 2 * key_dim + value_dim,
            2 * key_dim + 2 * value_dim)
    cols = lambda t, i: jnp.moveaxis(
        t[..., cuts[i]:cuts[i + 1]].reshape(t.shape[:-1] + (groups, -1)),
        -2, 0)
    heads = lambda t: jnp.moveaxis(
        t.reshape(t.shape[:-1] + (groups, hv)), -2, 0)

    def group(inputs):
        q, k, v, z, taps_q, taps_k, taps_v, b, a, a_log, dt_bias = inputs
        q, k, v = (jax.nn.silu(causal_depthwise_conv(t, taps_t))
                   for t, taps_t in ((q, taps_q), (k, taps_k), (v, taps_v)))
        q, k = (jnp.repeat(l2_normalize(t.reshape(bsz, s, hk, dk)), rep,
                           axis=2) for t in (q, k))
        # decay and write strength are float32 in the program whatever the
        # activations' type; a bf16 control rounds them with all the rest
        g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
        o = delta_rule(q / math.sqrt(dk), k, v.reshape(bsz, s, hv, dv),
                       g.astype(u.dtype), jax.nn.sigmoid(b),
                       carry_state=carry_state, correction=correction)
        gated = rms_norm(o, w["linear_attn.norm_weight"], eps) \
            * jax.nn.silu(z.reshape(bsz, s, hv, dv))
        return gated.reshape(bsz, s, hv * dv)

    out = jax.lax.map(jax.checkpoint(group), (
        cols(qkvz, 0), cols(qkvz, 1), cols(qkvz, 2), cols(qkvz, 3),
        cols(taps, 0), cols(taps, 1), cols(taps, 2),
        heads(ba[..., :value_heads]), heads(ba[..., value_heads:]),
        heads(w["linear_attn.A_log"]), heads(w["linear_attn.dt_bias"])))
    return jnp.moveaxis(out, 0, -2).reshape(bsz, s, value_dim) \
        @ w["linear_attn.out_proj.weight"]


# -- gated attention ------------------------------------------------------------

def rotary(x, rotary_dim, theta):
    """x (b, s, H, d): lanes 0 .. rotary_dim - 1 turned by the position,
    lane i < rotary_dim / 2 paired with lane i + rotary_dim / 2 at the
    angle ``position * theta^(-2 i / rotary_dim)``; the others untouched."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / rotary_dim)
    angle = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv_freq
    cos = jnp.cos(angle)[:, None, :].astype(x.dtype)
    sin = jnp.sin(angle)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary_dim:]], axis=-1)


def causal_attention(q, k, v, block=256):
    """``softmax(q k^T / sqrt(d)) v`` with the causal mask over q, k, v (b,
    s, H, d): a block of query rows at a time (``lax.map``), and for it a
    ``lax.scan`` over blocks of keys carrying the running maximum, the
    running sum and the unnormalised output."""
    bsz, s, h, d = q.shape
    block = min(block, s)
    pad = -s % block
    if pad:     # padded keys lie after every real query: never visible
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
    n = (s + pad) // block
    kb, vb = (jnp.moveaxis(t.reshape(bsz, n, block, h, d), 1, 0)
              for t in (k, v))
    starts = jnp.arange(n) * block

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        at = start + jnp.arange(block)

        def keys(carry, inputs):
            top, total, out = carry
            k_i, v_i, key_start = inputs
            scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k_i) / math.sqrt(d)
            visible = (key_start + jnp.arange(block))[None, :] <= at[:, None]
            scores = jnp.where(visible[None, None], scores, -jnp.inf)
            new_top = jnp.maximum(top, jnp.max(scores, axis=-1))
            shrink = jnp.exp(top - new_top)
            probs = jnp.exp(scores - new_top[..., None])
            return (new_top, total * shrink + jnp.sum(probs, axis=-1),
                    out * shrink[..., None]
                    + jnp.einsum("bhqk,bkhd->bhqd", probs, v_i)), None

        # key block 0 comes first and every query sees key 0, so the
        # running maximum is finite from the first step on
        (_, total, out), _ = jax.lax.scan(
            keys, (jnp.full((bsz, h, block), -jnp.inf, q.dtype),
                   jnp.zeros((bsz, h, block), q.dtype),
                   jnp.zeros((bsz, h, block, d), q.dtype)),
            (kb, vb, starts))
        return jnp.moveaxis(out / total[..., None], 1, 2)   # (b, block, H, d)

    # under jax.grad a block of rows is made again, never kept
    out = jax.lax.map(jax.checkpoint(rows), starts)
    return jnp.moveaxis(out, 0, 1).reshape(bsz, s + pad, h, d)[:, :s]


def gated_attention(u, w: dict, heads, kv_heads, head_dim, rotary_dim,
                    theta, eps):
    """The mixer, one key/value head with its query heads after the other
    (``lax.map``, each a ``jax.checkpoint``)."""
    bsz, s, _ = u.shape
    rep = heads // kv_heads
    qg = (u @ w["self_attn.q_proj.weight"]).reshape(bsz, s, kv_heads, rep,
                                                    2 * head_dim)
    k = (u @ w["self_attn.k_proj.weight"]).reshape(bsz, s, kv_heads, head_dim)
    v = (u @ w["self_attn.v_proj.weight"]).reshape(bsz, s, kv_heads, head_dim)

    def group(inputs):
        qg, k, v = inputs            # (b, s, rep, 2 d), (b, s, d), (b, s, d)
        q, gate = qg[..., :head_dim], qg[..., head_dim:]
        q = rotary(rms_norm(q, 1.0 + w["self_attn.q_norm.weight"], eps),
                   rotary_dim, theta)
        k = rotary(rms_norm(k[:, :, None],
                            1.0 + w["self_attn.k_norm.weight"], eps),
                   rotary_dim, theta)
        k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v[:, :, None]))
        return causal_attention(q, k, v) * jax.nn.sigmoid(gate)

    out = jax.lax.map(jax.checkpoint(group), tuple(
        jnp.moveaxis(t, 2, 0) for t in (qg, k, v)))      # (kv, b, s, rep, d)
    return jnp.moveaxis(out, 0, 2).reshape(bsz, s, heads * head_dim) \
        @ w["self_attn.o_proj.weight"]


# -- experts --------------------------------------------------------------------

def gated_mlp(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def route(u, w: dict, top_k, chosen=None):
    """(chosen (b, s, k) expert ids, weights (b, s, k) in ``u``'s type).
    The probabilities are computed in the type of the router's weight:
    float32 for the reference, bfloat16 for the control without a float32
    router.  With ``chosen`` given the choice is taken from there (a probe:
    what another run chose) and the weights are this run's probabilities of
    it."""
    router = w["mlp.gate.weight"]
    probs = jax.nn.softmax(u.astype(router.dtype) @ router, axis=-1)
    if chosen is None:
        _, chosen = jax.lax.top_k(probs, top_k)
    picked = jnp.take_along_axis(probs, chosen, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen, weights.astype(u.dtype)


def experts(u, w: dict, held, top_k, with_shared=True, chosen=None):
    """The held experts' part of the layer plus the gated shared expert.
    ``held[j]`` is the router's id of the expert whose weights are
    ``gate_proj[j]``, ``up_proj[j]`` and ``down_proj[j]``."""
    chosen, weights = route(u, w, top_k, chosen)

    # under jax.grad an expert keeps its weights alone and is made again
    @jax.checkpoint
    def part(expert_id, w_gate, w_up, w_down):
        gate = jnp.sum(jnp.where(chosen == expert_id, weights, 0.0), axis=-1)
        return gate[..., None] * gated_mlp(u, w_gate, w_up, w_down)

    # the held experts one after the other: a scan over the list, so that
    # the program holds one expert's arrays (and code) at a time
    out, _ = jax.lax.scan(
        lambda out, one: (out + part(*one), None), jnp.zeros_like(u),
        (jnp.asarray(held), w["mlp.experts.gate_proj"],
         w["mlp.experts.up_proj"], w["mlp.experts.down_proj"]))
    if with_shared:
        out = out + shared_expert(u, w)
    return out


def shared_expert(u, w: dict):
    return jax.nn.sigmoid(
        jnp.sum(u * w["mlp.shared_gate"], axis=-1, keepdims=True)
    ) * gated_mlp(u, w["mlp.shared_experts.gate_proj.weight"],
                  w["mlp.shared_experts.up_proj.weight"],
                  w["mlp.shared_experts.down_proj.weight"])


# -- the model ------------------------------------------------------------------

def _typed(w: dict, dtype, router_dtype) -> dict:
    return {k: jnp.asarray(v).astype(
        router_dtype if k in ROUTER and router_dtype else dtype)
        for k, v in w.items()}


def mixer(x, w: dict, kind: str, model: dict, carry_state=True,
          correction=True):
    """``x + mixer(N(x))`` of a layer of ``kind``, ``w`` already typed."""
    eps = model["rms_norm_eps"]
    u = rms_norm(x, 1.0 + w["input_layernorm.weight"], eps)
    if kind == LINEAR:
        out = gated_delta_net(
            u, w, model["linear_num_key_heads"],
            model["linear_num_value_heads"], model["linear_key_head_dim"],
            model["linear_value_head_dim"], eps, carry_state, correction)
    else:
        out = gated_attention(
            u, w, model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"],
            int(model["head_dim"] * model["partial_rotary_factor"]),
            model["rope_theta"], eps)
    return x + out.astype(x.dtype)


def choice(x, w: dict, kind: str, model: dict, dtype=F32, router_dtype=None):
    """The experts the expert layer of a layer of these types chooses,
    given the layer's input ``x``: (b, s, k) ids, as :func:`layer` computes
    them."""
    with jax.default_matmul_precision("highest"):
        w = _typed(w, dtype, router_dtype)
        x = mixer(x, w, kind, model)
        u = rms_norm(x, 1.0 + w["post_attention_layernorm.weight"],
                     model["rms_norm_eps"])
        return route(u, w, model["num_experts_per_tok"])[0]


def layer(x, w: dict, kind: str, model: dict, dtype=F32, with_experts=True,
          carry_state=True, correction=True, router_dtype=None, chosen=None):
    """One layer on activations (b, s, h) of ``dtype``.  ``model`` holds the
    sizes by the configuration's names.  The three flags are the controls:
    a reference without its expert layers, with a delta rule that forgets
    its state between blocks or that drops its correction term, must read
    not correct.  ``router_dtype`` (default ``dtype``) is the type of the
    router's weight and probabilities; ``chosen`` as :func:`route` takes
    it."""
    with jax.default_matmul_precision("highest"):
        w = _typed(w, dtype, router_dtype)
        x = mixer(x, w, kind, model, carry_state, correction)
        if not with_experts:
            return x
        u = rms_norm(x, 1.0 + w["post_attention_layernorm.weight"],
                     model["rms_norm_eps"])
        return x + experts(u, w, model["held_experts"],
                           model["num_experts_per_tok"],
                           chosen=chosen).astype(dtype)


def head(x, norm_w, head_w, eps, dtype=F32):
    """Final norm and the untied head: (b, s, h) -> (b, s, V) float32."""
    with jax.default_matmul_precision("highest"):
        return (rms_norm(x, 1.0 + jnp.asarray(norm_w).astype(dtype), eps)
                @ jnp.asarray(head_w).astype(dtype)).astype(F32)


def hidden(weights: dict, ids, model: dict, dtype=F32, remat=False, **flags):
    """The layers' output (b, s, h) in ``dtype``, before the final norm."""
    x = embed(weights["model.embed_tokens.weight"], ids, dtype)
    for i, kind in enumerate(layer_types(model)):
        run = functools.partial(layer, kind=kind, model=model, dtype=dtype,
                                **flags)
        if remat:
            run = jax.checkpoint(run)
        x = run(x, layer_weights(weights, i, kind))
    return x


def forward(weights: dict, ids, model: dict, dtype=F32, **flags):
    """Logits (b, s, V) in float32 for token ids (b, s)."""
    return head(hidden(weights, ids, model, dtype, **flags),
                weights["model.norm.weight"], weights["lm_head.weight"],
                model["rms_norm_eps"], dtype)


def token_losses(logits, ids):
    """Next-token cross-entropy at positions 0..s-2 of each row: (b, s-1)."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(F32), axis=-1)
    return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]


def loss(weights: dict, ids, model: dict, dtype=F32, rows=2048, **flags):
    """Mean next-token cross-entropy (every position but the last predicts
    its successor), recomputed a layer at a time under ``jax.grad``, the
    head and the losses ``rows`` positions at a time (each block a
    ``jax.checkpoint``): a row of 16k tokens never holds its (s, V)
    logits, their log-softmax and their gradient at once."""
    x = hidden(weights, ids, model, dtype, remat=True, **flags)
    bsz, s, h = x.shape
    rows = min(rows, s)
    pad = -s % rows
    # position t predicts token t + 1; the last predicts nothing
    targets = jnp.pad(ids[:, 1:], ((0, 0), (0, pad + 1)))
    counted = jnp.pad(jnp.ones((bsz, s - 1), F32), ((0, 0), (0, pad + 1)))
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    blocks = lambda t: jnp.moveaxis(
        t.reshape((bsz, -1, rows) + t.shape[2:]), 1, 0)

    @jax.checkpoint
    def block(x_b, targets_b, counted_b):
        logits = head(x_b, weights["model.norm.weight"],
                      weights["lm_head.weight"], model["rms_norm_eps"], dtype)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, targets_b[..., None],
                                     axis=-1)[..., 0]
        return -jnp.sum(picked * counted_b)

    total, _ = jax.lax.scan(
        lambda total, b: (total + block(*b), None), jnp.zeros((), F32),
        (blocks(x), blocks(targets), blocks(counted)))
    return total / (bsz * (s - 1))
