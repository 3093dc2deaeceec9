"""Everything random in a run is a function of ``--seed`` and a stream name.

``--seed`` may exceed 2**31 (the driver's do), so it never goes into a
``RandomState`` or an int32 key: ``numpy.random.SeedSequence`` takes any
non-negative whole number and hands out 32-bit words.
"""
from __future__ import annotations

import zlib

import numpy as np


def _sequence(seed: int, stream: str) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed), zlib.crc32(stream.encode())])


def rng(seed: int, stream: str) -> np.random.Generator:
    """A numpy generator for one named stream of this seed."""
    return np.random.default_rng(_sequence(seed, stream))


def key_words(seed: int, stream: str) -> np.ndarray:
    """Two uint32 words: the data of a threefry key, passed to jitted code
    as an ARGUMENT (a key closed over would be compiled in, and every seed
    would then compile its own program)."""
    return _sequence(seed, stream).generate_state(2, np.uint32)


def small_seed(seed: int) -> int:
    """``seed`` folded into 31 bits, for program interfaces that take an
    int (``paddle.seed``, ``DecodeEngine(seed=)``)."""
    return int(_sequence(seed, "small").generate_state(1, np.uint32)[0]
               & 0x7FFFFFFF)
