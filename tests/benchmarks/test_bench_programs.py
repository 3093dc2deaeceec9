"""Nothing that depends on the seed is closed over by a jitted function:
every program of every cell lowers to the same text for two seeds, so the
second process of a chip call finds all of them in the compile cache."""
import gc

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from benchmarks.lib import harness, seeds, train, weights as weights_mod

SEED_A, SEED_B = 11, 2 ** 31 + 252


def train_step_text(tiny_config, seed, amp):
    from bench_support import QuietRun
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPTPretrainingCriterion
    model, cfg, _ = harness.build_model(QuietRun(seed), tiny_config, amp)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(parameters=model.parameters(),
                                 learning_rate=1e-4, weight_decay=0.01)
    step = TrainStep(model, lambda logits, labels: crit(logits, labels), opt)
    x = train._make_batches(jnp.asarray(seeds.key_words(seed, "batches")),
                            2, 2, 64, 500)[0]
    return step._step.lower(*step.trace_args((x, x))).as_text(), step


@pytest.mark.parametrize("amp", [False, True])
def test_train_step_text_is_the_same_for_two_seeds(tiny_config, amp):
    a, _ = train_step_text(tiny_config, SEED_A, amp)
    b, _ = train_step_text(tiny_config, SEED_B, amp)
    assert a == b
    # and the weights are arguments, not constants of the program
    assert len(a) < 2_000_000


def test_reset_to_seed_restores_the_state_and_compiles_nothing(tiny_config):
    from paddle_tpu import observability as obs
    _, step = train_step_text(tiny_config, SEED_A, True)
    x = train._make_batches(jnp.asarray(seeds.key_words(SEED_A, "batches")),
                            2, 2, 64, 500)
    first = float(step(x[0], x[0]).numpy())
    step(x[1], x[1]).numpy()
    train._reset_to_seed(step, SEED_A)
    again = float(step(x[0], x[0]).numpy())
    assert again == first
    assert obs.compile_counts()["jit.train_step"] == 1
    del step
    gc.collect()


def test_generators_take_the_seed_as_an_argument():
    like = {"gpt.wte.weight": jax.ShapeDtypeStruct((64, 8), jnp.bfloat16),
            "gpt.h.0.ln1.weight": jax.ShapeDtypeStruct((8,), jnp.float32),
            "gpt.h.0.mlp.fc2.weight": jax.ShapeDtypeStruct((32, 8),
                                                           jnp.bfloat16),
            "gpt.h.0.mlp.fc2.bias": jax.ShapeDtypeStruct((8,), jnp.bfloat16)}
    spec = tuple((k, tuple(v.shape), jnp.dtype(v.dtype).name)
                 for k, v in sorted(like.items()))
    texts = [weights_mod._make.lower(
        jnp.asarray(seeds.key_words(s, "weights")), spec, 0.02, 2).as_text()
        for s in (SEED_A, SEED_B)]
    assert texts[0] == texts[1]
    a = weights_mod.make_weights(seeds.key_words(SEED_A, "weights"), like,
                                 0.02, 2)
    b = weights_mod.make_weights(seeds.key_words(SEED_B, "weights"), like,
                                 0.02, 2)
    assert a["gpt.wte.weight"].dtype == jnp.bfloat16
    assert not bool(jnp.all(a["gpt.wte.weight"] == b["gpt.wte.weight"]))
    assert bool(jnp.all(a["gpt.h.0.ln1.weight"] == 1.0))
    assert bool(jnp.all(a["gpt.h.0.mlp.fc2.bias"] == 0.0))
    # the residual projections are drawn narrower: 0.02 / sqrt(2 L)
    assert float(jnp.std(a["gpt.h.0.mlp.fc2.weight"].astype(
        jnp.float32))) < 0.015
    again = weights_mod.make_weights(seeds.key_words(SEED_A, "weights"),
                                     like, 0.02, 2)
    assert bool(jnp.all(again["gpt.wte.weight"] == a["gpt.wte.weight"]))


def test_serving_programs_text_is_the_same_for_two_seeds(tiny_config):
    from bench_support import QuietRun
    from paddle_tpu.serving.engine import DecodeEngine
    texts = []
    for seed in (SEED_A, SEED_B):
        model, _, _ = harness.build_model(QuietRun(seed), tiny_config,
                                          amp=True)
        model.eval()
        engine = DecodeEngine(model, num_slots=3, max_len=64, page_size=16,
                              num_pages=12, seed=seeds.small_seed(seed))
        texts.append((
            jax.jit(engine._decode_fn).lower(
                *engine.decode_trace_args()).as_text(),
            jax.jit(engine._prefill_chunk_fn).lower(
                *engine.prefill_chunk_trace_args()).as_text()))
        del engine
        gc.collect()
    assert texts[0][0] == texts[1][0]
    assert texts[0][1] == texts[1][1]
