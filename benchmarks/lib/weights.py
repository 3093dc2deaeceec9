"""The model's weights from the seed, on the device, in one jitted call.

The program initialises a model leaf by leaf with eager ops; the benchmark
overwrites every leaf with values drawn here, so that the weights are a
function of ``--seed`` alone and the reference can be given exactly the same
ones.  Initialisation follows GPT-2: N(0, 0.02) for embeddings and matrices,
N(0, 0.02 / sqrt(2 L)) for the two projections that write into the residual
stream, ones and zeros for LayerNorm and biases.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _std_of(name: str, init_range: float, num_layers: int):
    """None for a constant leaf, else the normal's standard deviation."""
    if name.endswith(".bias"):
        return None
    if ".ln" in name or name.endswith("ln_f.weight"):
        return None
    if name.endswith("out_proj.weight") or name.endswith("fc2.weight"):
        return init_range / math.sqrt(2.0 * num_layers)
    return init_range


@functools.partial(jax.jit, static_argnames=("spec", "init_range",
                                             "num_layers"))
def _make(words, spec, init_range, num_layers):
    key = jax.random.wrap_key_data(words.astype(jnp.uint32),
                                   impl="threefry2x32")
    out = {}
    for i, (name, shape, dtype) in enumerate(spec):
        std = _std_of(name, init_range, num_layers)
        if std is None:
            fill = 1.0 if name.endswith(".weight") else 0.0
            out[name] = jnp.full(shape, fill, dtype)
        else:
            out[name] = (std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            ).astype(dtype)
    return out


def make_weights(words, like: dict, init_range: float, num_layers: int):
    """A dict with the names, shapes and dtypes of ``like`` (a model's
    ``functional_state()``), drawn from the key data ``words``."""
    spec = tuple((name, tuple(v.shape), jnp.dtype(v.dtype).name)
                 for name, v in sorted(like.items()))
    return _make(jnp.asarray(words), spec, float(init_range),
                 int(num_layers))
