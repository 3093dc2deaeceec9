"""The model's weights from the seed, on the device, in one jitted call.

The program initialises a model leaf by leaf with eager ops; the benchmark
overwrites every leaf with values drawn here, so that the weights are a
function of ``--seed`` alone and the reference can be given exactly the same
ones.  The configuration's family says how each leaf is drawn
(``init_of(name, model)``: a normal's standard deviation, a constant, or
the interval of a uniform: a state-space layer's decay and step size are
drawn from one in every published initialisation, and were they constant
every head would decay alike, so that a head indexed wrongly could not be
seen).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("spec",))
def _make(words, spec):
    key = jax.random.wrap_key_data(words.astype(jnp.uint32),
                                   impl="threefry2x32")
    out = {}
    for i, (name, shape, dtype, (how, value)) in enumerate(spec):
        if how == "constant":
            out[name] = jnp.full(shape, value, dtype)
        elif how == "normal":
            out[name] = (value * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            ).astype(dtype)
        elif how == "uniform":
            low, high = value
            out[name] = jax.random.uniform(
                jax.random.fold_in(key, i), shape, jnp.float32, low, high
            ).astype(dtype)
        else:
            raise ValueError("leaf %s: unknown initialisation %r"
                             % (name, how))
    return out


def leaf_spec(like: dict, family, model: dict) -> tuple:
    """``like`` (a model's ``functional_state()``) as the static argument of
    the one program: (name, shape, dtype, the family's rule) by name."""
    def rule(how, value):     # hashable: a uniform's interval is a pair
        return (how, tuple(value) if how == "uniform" else value)
    return tuple((name, tuple(v.shape), jnp.dtype(v.dtype).name,
                  rule(*family.init_of(name, model)))
                 for name, v in sorted(like.items()))


def make_weights(words, like: dict, family, model: dict):
    """A dict with the names, shapes and dtypes of ``like``, drawn from the
    key data ``words`` by the rule of ``family`` for the model dict
    ``model``."""
    return _make(jnp.asarray(words), leaf_spec(like, family, model))
