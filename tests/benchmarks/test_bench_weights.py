"""``lib/weights.py``'s initialisation kinds: ``normal`` and ``constant``
draw the ``gpt`` family's weights bit for bit as before PR 29 added
``uniform``; a uniform leaf lies in its interval, is a function of the seed
and of its place among the leaves, and is drawn in the same one program."""
import types

import jax
import jax.numpy as jnp
import pytest

from benchmarks.families import gpt as gpt_family
from benchmarks.lib import seeds, weights as weights_mod
from test_bench_programs import parent_make_weights

MODEL = {"initializer_range": 0.02, "num_hidden_layers": 2}
GPT_LIKE = {
    "gpt.wte.weight": jax.ShapeDtypeStruct((64, 8), jnp.bfloat16),
    "gpt.h.0.ln1.weight": jax.ShapeDtypeStruct((8,), jnp.float32),
    "gpt.h.0.mlp.fc1.weight": jax.ShapeDtypeStruct((8, 32), jnp.bfloat16),
    "gpt.h.0.mlp.fc2.weight": jax.ShapeDtypeStruct((32, 8), jnp.bfloat16),
    "gpt.h.0.mlp.fc2.bias": jax.ShapeDtypeStruct((8,), jnp.bfloat16)}
#: a state-space layer's decay and step size, by the names Mamba-2 gives
#: them; they sort after every ``gpt.`` leaf, so those keep their places
SSM_LIKE = {
    "ssm.0.A_log": jax.ShapeDtypeStruct((24,), jnp.float32),
    "ssm.0.dt_bias": jax.ShapeDtypeStruct((24,), jnp.bfloat16)}
INTERVALS = {"ssm.0.A_log": (0.0, 2.772588722), "ssm.0.dt_bias": [-6.9, -2.3]}


def init_of(name, model):
    if name in INTERVALS:
        return ("uniform", INTERVALS[name])      # a pair or a list
    return gpt_family.init_of(name, model)


mixed_family = types.SimpleNamespace(init_of=init_of)


def words(seed):
    return seeds.key_words(seed, "weights")


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 252, 4_000_000_007])
def test_gpts_weights_are_bit_for_bit_what_they_were(seed):
    was = parent_make_weights(words(seed), GPT_LIKE, 0.02, 2)
    now = weights_mod.make_weights(words(seed), GPT_LIKE, gpt_family, MODEL)
    # and beside leaves of the new kind the old ones are drawn as before
    mixed = weights_mod.make_weights(
        words(seed), {**GPT_LIKE, **SSM_LIKE}, mixed_family, MODEL)
    assert sorted(now) == sorted(was)
    for name in was:
        assert now[name].dtype == was[name].dtype, name
        assert bool(jnp.all(now[name] == was[name])), name
        assert bool(jnp.all(mixed[name] == was[name])), name


def test_a_uniform_leaf_lies_in_its_interval_and_follows_the_seed():
    like = {**GPT_LIKE, **SSM_LIKE}
    a = weights_mod.make_weights(words(11), like, mixed_family, MODEL)
    for name, (low, high) in INTERVALS.items():
        leaf = a[name]
        assert leaf.dtype == like[name].dtype and leaf.shape == (24,)
        values = leaf.astype(jnp.float32)
        # drawn in float32 inside [low, high); bf16 rounds outward a little
        assert float(values.min()) >= (low if leaf.dtype == jnp.float32
                                       else low - 0.02)
        assert float(values.max()) <= (high if leaf.dtype == jnp.float32
                                       else high + 0.02)
        # spread over the interval, not one value a head
        assert len(set(values.tolist())) > 12
        assert float(values.max() - values.min()) > 0.5 * (high - low)
    again = weights_mod.make_weights(words(11), like, mixed_family, MODEL)
    other = weights_mod.make_weights(words(12), like, mixed_family, MODEL)
    for name in INTERVALS:
        assert bool(jnp.all(again[name] == a[name]))
        assert not bool(jnp.all(other[name] == a[name]))
    # two leaves of one interval are not one draw: one ``fold_in`` a leaf
    same = types.SimpleNamespace(init_of=lambda name, model: (
        "uniform", (0.0, 1.0)))
    both = weights_mod.make_weights(
        words(11), {"a": SSM_LIKE["ssm.0.A_log"],
                    "b": SSM_LIKE["ssm.0.A_log"]}, same, MODEL)
    assert not bool(jnp.all(both["a"] == both["b"]))


def test_the_seed_is_an_argument_of_the_one_program_with_uniform_leaves():
    spec = weights_mod.leaf_spec({**GPT_LIKE, **SSM_LIKE}, mixed_family,
                                 MODEL)
    hash(spec)                       # static argument of the jitted program
    assert ("ssm.0.dt_bias", (24,), "bfloat16",
            ("uniform", (-6.9, -2.3))) in spec
    texts = [weights_mod._make.lower(jnp.asarray(words(s)), spec).as_text()
             for s in (11, 2 ** 31 + 252)]
    assert texts[0] == texts[1]


def test_an_unknown_kind_is_refused():
    odd = types.SimpleNamespace(init_of=lambda name, model: ("lognormal", 1))
    with pytest.raises(ValueError, match="unknown initialisation"):
        weights_mod.make_weights(words(1), SSM_LIKE, odd, MODEL)
