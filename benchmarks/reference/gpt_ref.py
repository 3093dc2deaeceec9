"""Plain reference for the GPT-2 family: forward pass, loss, nothing else.

Straight ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``
(on a TPU a float32 matmul otherwise runs in bf16 passes).  No kernels, no
cache, no batching tricks; imports nothing from ``paddle_tpu``.  It follows
Radford et al. 2019 (GPT-2) as published in ``openai-community/gpt2``:
pre-LayerNorm blocks, learned positions, GELU (tanh form), tied output head.
Cerebras-GPT (arXiv:2304.03208) uses the same block equations.

The weights are an ARGUMENT, a dict by the names the model gives them
(``gpt.wte.weight``, ``gpt.h.<i>.attn.qkv_proj.weight`` ...; linear weights
are stored ``(in, out)``).  They may arrive in the type they are served in;
each is widened to float32 here, which is exact.  Departure from the paper:
the vocabulary may be padded (50,257 -> 50,304); the padded rows are ordinary
rows of the embedding and take part in the softmax, as in the system.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
LAYER_FIELDS = ("ln1.weight", "ln1.bias", "attn.qkv_proj.weight",
                "attn.qkv_proj.bias", "attn.out_proj.weight",
                "attn.out_proj.bias", "ln2.weight", "ln2.bias",
                "mlp.fc1.weight", "mlp.fc1.bias", "mlp.fc2.weight",
                "mlp.fc2.bias")


def _f32(x):
    return jnp.asarray(x).astype(F32)


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight + bias


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def layer_weights(weights: dict, i: int) -> dict:
    """The twelve tensors of block ``i``, by their field names."""
    return {f: weights["gpt.h.%d.%s" % (i, f)] for f in LAYER_FIELDS}


def embed(wte, wpe, ids):
    """ids: (b, s) int -> (b, s, h) float32."""
    s = ids.shape[1]
    return _f32(wte)[ids] + _f32(wpe)[jnp.arange(s)][None]


def block(x, w: dict, num_heads: int, eps: float = 1e-5):
    """One pre-LN transformer block on float32 activations (b, s, h)."""
    with jax.default_matmul_precision("highest"):
        w = {k: _f32(v) for k, v in w.items()}
        b, s, h = x.shape
        d = h // num_heads
        a = layer_norm(x, w["ln1.weight"], w["ln1.bias"], eps)
        qkv = a @ w["attn.qkv_proj.weight"] + w["attn.qkv_proj.bias"]
        q, k, v = (qkv[..., i * h:(i + 1) * h].reshape(b, s, num_heads, d)
                   for i in range(3))
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(d)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bnqk,bknd->bqnd", probs, v).reshape(b, s, h)
        x = x + attn @ w["attn.out_proj.weight"] + w["attn.out_proj.bias"]
        m = layer_norm(x, w["ln2.weight"], w["ln2.bias"], eps)
        m = gelu_tanh(m @ w["mlp.fc1.weight"] + w["mlp.fc1.bias"])
        return x + m @ w["mlp.fc2.weight"] + w["mlp.fc2.bias"]


def head(x, ln_w, ln_b, wte, eps: float = 1e-5):
    """Final LayerNorm and the tied output head: (b, s, h) -> (b, s, V)."""
    with jax.default_matmul_precision("highest"):
        return layer_norm(x, _f32(ln_w), _f32(ln_b), eps) @ _f32(wte).T


def forward(weights: dict, ids, num_layers: int, num_heads: int,
            eps: float = 1e-5):
    """Logits (b, s, V) in float32 for token ids (b, s)."""
    x = embed(weights["gpt.wte.weight"], weights["gpt.wpe.weight"], ids)
    for i in range(num_layers):
        x = block(x, layer_weights(weights, i), num_heads, eps)
    return head(x, weights["gpt.ln_f.weight"], weights["gpt.ln_f.bias"],
                weights["gpt.wte.weight"], eps)


def token_losses(logits, ids):
    """Next-token cross-entropy at positions 0..s-2 of each row: (b, s-1)."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(F32), axis=-1)
    return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)[..., 0]


def loss(weights: dict, ids, num_layers: int, num_heads: int,
         eps: float = 1e-5):
    """Mean next-token cross-entropy over the batch (the pre-training
    loss: every position but the last predicts its successor)."""
    return jnp.mean(token_losses(
        forward(weights, ids, num_layers, num_heads, eps), ids))
