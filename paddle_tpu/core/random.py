"""Global PRNG management.

TPU-native rethink of the reference's generator registry
(reference: paddle/fluid/framework/generator.cc, python/paddle/framework/random.py):
instead of stateful per-device Philox generators, a root ``jax.random`` key
plus a monotonically increasing fold-in counter.  Layers that need randomness
(dropout, random init) draw fresh keys from the default generator; compiled
step functions instead thread an explicit key (see paddle_tpu.jit) through a
scoped override so traces stay functional.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp


class Generator:
    """A stream of PRNG keys derived from one root seed."""

    def __init__(self, seed: int = 0):
        self.manual_seed(seed)

    def manual_seed(self, seed: int):
        # the root key is built on first use, not here: creating it runs a
        # jax computation, and the module-level default generator would make
        # `import paddle_tpu` initialise the backend — on a TPU host that
        # takes the chip from whichever process the importer meant to start
        self._seed = int(seed)
        self._key = None
        self._counter = 0
        return self

    def seed(self, seed: int):
        return self.manual_seed(seed)

    @property
    def initial_seed(self):
        return self._seed

    def next_key(self):
        if self._key is None:
            self._key = jax.random.key(self._seed)
        self._counter += 1
        return jax.random.fold_in(self._key, self._counter)

    def split(self, n: int):
        return jax.random.split(self.next_key(), n)

    def get_state(self):
        return {"seed": self._seed, "counter": self._counter}

    def set_state(self, state):
        self.manual_seed(state["seed"])
        self._counter = int(state["counter"])


_default_generator = Generator(0)

# When a compiled trace supplies an explicit key stream, it is pushed here so
# layer-level randomness (dropout) becomes a pure function of that key.
_key_stream_stack = []


def default_generator() -> Generator:
    return _default_generator


def seed(s: int):
    """paddle.seed equivalent — reseed the global generator."""
    _default_generator.manual_seed(s)
    return _default_generator


def get_rng_state():
    return _default_generator.get_state()


def set_rng_state(state):
    _default_generator.set_state(state)


class _KeyStream:
    """Functional key stream: fold_in over an explicit base key.

    Safe under jit tracing — the fold-in counter advances at trace time, so
    every dropout site in a traced step gets a distinct, deterministic subkey
    of the step's key argument.
    """

    def __init__(self, base_key):
        self.base_key = base_key
        self._counter = 0

    def next_key(self):
        self._counter += 1
        return jax.random.fold_in(self.base_key, self._counter)


@contextlib.contextmanager
def key_stream(base_key):
    """Scope in which layer randomness draws from ``base_key``."""
    stream = _KeyStream(base_key)
    _key_stream_stack.append(stream)
    try:
        yield stream
    finally:
        _key_stream_stack.pop()


def next_key():
    """Fresh PRNG key: from the innermost explicit stream if any, else the
    global eager generator."""
    if _key_stream_stack:
        return _key_stream_stack[-1].next_key()
    return _default_generator.next_key()
