"""The plain reference against the program's model at tiny size on the CPU,
over five seeds, and the mutations that show each tolerance discriminates:
fp8 weights and a dropped residual must FAIL what the clean system passes."""
import functools

import jax
import jax.numpy as jnp
import pytest

from benchmarks.lib import check, harness, seeds
from benchmarks.reference import gpt_ref

SEEDS = [0, 1, 31337, 2 ** 31 + 252, 4_000_000_007]


@pytest.fixture(scope="module")
def built(tiny_config):
    """{seed: (model in bf16 as served, cfg, weights)}; one model object
    per seed, built as the serving runner builds it."""
    from bench_support import QuietRun
    out = {}
    for seed in SEEDS:
        out[seed] = harness.build_model(QuietRun(seed), tiny_config, amp=True)
        out[seed][0].eval()
    return out


def sample_ids(seed, cfg, rows=2, width=96):
    return jnp.asarray(seeds.rng(seed, "ids").integers(
        0, 500, (rows, width)), jnp.int32)


def ref_logits(cfg, weights, ids):
    return check.reference_forward_fn(
        cfg.num_hidden_layers, cfg.num_attention_heads,
        cfg.layer_norm_epsilon)(weights, ids)


@pytest.mark.parametrize("seed", SEEDS)
def test_system_in_bf16_is_within_tolerance(built, seed):
    model, cfg, weights = built[seed]
    ids = sample_ids(seed, cfg)
    errors = check.logits_errors(
        check.system_forward_fn(model)(weights, ids),
        ref_logits(cfg, weights, ids))
    assert errors["finite"]
    # basis: the widest of these five is 0.0053 (check.BASIS)
    assert errors["rel_rms"] < 0.008
    assert errors["rel_rms"] < check.LOGITS_RMS_TOL


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_equals_float32_model(tiny_config, seed):
    """In float32 the program's model and the reference are the same
    function: what the tolerance allows is bf16, not a different model."""
    from bench_support import QuietRun
    model, cfg, weights = harness.build_model(QuietRun(seed), tiny_config,
                                              amp=False)
    model.eval()
    ids = sample_ids(seed, cfg)
    errors = check.logits_errors(
        check.system_forward_fn(model)(weights, ids),
        ref_logits(cfg, weights, ids))
    assert errors["rel_max"] < 1e-4


def fp8_weights(weights):
    return {k: (v.astype(jnp.float8_e4m3fn).astype(v.dtype)
                if v.ndim == 2 else v) for k, v in weights.items()}


@pytest.mark.parametrize("seed", SEEDS)
def test_fp8_weights_fail_the_logits_tolerance(built, seed):
    model, cfg, weights = built[seed]
    ids = sample_ids(seed, cfg)
    errors = check.logits_errors(
        check.system_forward_fn(model)(fp8_weights(weights), ids),
        ref_logits(cfg, weights, ids))
    assert errors["rel_rms"] > 2 * check.LOGITS_RMS_TOL


@pytest.mark.parametrize("seed", SEEDS)
def test_dropped_residual_fails_the_logits_tolerance(built, seed):
    """A 'system' that leaves the MLP's residual add out of block 0."""
    model, cfg, weights = built[seed]
    ids = sample_ids(seed, cfg)

    def mutant(weights, ids):
        x = gpt_ref.embed(weights["gpt.wte.weight"],
                          weights["gpt.wpe.weight"], ids)
        for i in range(cfg.num_hidden_layers):
            w = gpt_ref.layer_weights(weights, i)
            if i == 0:
                w = dict(w, **{"mlp.fc2.weight":
                               jnp.zeros_like(w["mlp.fc2.weight"])})
            x = gpt_ref.block(x, w, cfg.num_attention_heads)
        return gpt_ref.head(x, weights["gpt.ln_f.weight"],
                            weights["gpt.ln_f.bias"],
                            weights["gpt.wte.weight"])
    errors = check.logits_errors(mutant(weights, ids),
                                 ref_logits(cfg, weights, ids))
    assert errors["rel_rms"] > 2 * check.LOGITS_RMS_TOL


@pytest.mark.parametrize("seed", SEEDS[:3])
def test_served_deficit_rule(built, seed):
    """The reference's own greedy continuation has deficit 0; a stream of
    random tokens has a deficit far above the tolerance."""
    _, cfg, weights = built[seed]
    forward = check.reference_forward_fn(
        cfg.num_hidden_layers, cfg.num_attention_heads,
        cfg.layer_norm_epsilon)
    prompt = [int(t) for t in seeds.rng(seed, "p").integers(0, 500, (20,))]
    stream = []
    for _ in range(6):
        ids = jnp.zeros((1, 64), jnp.int32).at[0, :len(prompt) + len(
            stream)].set(jnp.asarray(prompt + stream, jnp.int32))
        logits = forward(weights, ids)[0, len(prompt) + len(stream) - 1]
        stream.append(int(jnp.argmax(logits)))
    assert check.served_deficit(forward, weights, [(prompt, stream)],
                                64) == 0.0
    wrong = [int(t) for t in seeds.rng(seed, "w").integers(0, 500, (6,))]
    assert check.served_deficit(forward, weights, [(prompt, wrong)],
                                64) > 3 * check.DEFICIT_TOL


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_gradient_comparison_and_its_mutations(tiny_config, seed):
    """PR 23's per-tensor gradient error: the clean float32 system agrees
    to rounding; fp8 weights and a dropped residual exceed the tolerance."""
    from bench_support import QuietRun
    _, cfg, weights = harness.build_model(QuietRun(seed), tiny_config,
                                          amp=False)
    ids = sample_ids(seed, cfg)
    loss = functools.partial(gpt_ref.loss, num_layers=cfg.num_hidden_layers,
                             num_heads=cfg.num_attention_heads)
    grad = jax.jit(jax.grad(loss))
    ref = grad(weights, ids)
    assert check.grad_errors(ref, ref)["worst"] == 0.0
    fp8 = check.grad_errors(grad(fp8_weights(weights), ids), ref)
    assert fp8["worst"] > check.GRAD_REL_TOL
    dropped = dict(weights, **{"gpt.h.0.mlp.fc2.weight": jnp.zeros_like(
        weights["gpt.h.0.mlp.fc2.weight"])})
    assert check.grad_errors(grad(dropped, ids),
                             ref)["worst"] > check.GRAD_REL_TOL


def test_reference_loss_by_rows_equals_whole_batch(built):
    _, cfg, weights = built[0]
    ids = sample_ids(0, cfg, rows=4)
    forward = check.reference_forward_fn(
        cfg.num_hidden_layers, cfg.num_attention_heads,
        cfg.layer_norm_epsilon)
    whole = float(gpt_ref.loss(weights, ids, cfg.num_hidden_layers,
                               cfg.num_attention_heads))
    assert check.reference_loss(forward, weights, ids) == pytest.approx(
        whole, rel=1e-6)


def test_reference_imports_nothing_from_the_program():
    import ast
    import inspect
    tree = ast.parse(inspect.getsource(gpt_ref))
    names = [n.module or "" for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom)]
    names += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
              for a in n.names]
    assert not any(n.startswith(("paddle", "benchmarks")) for n in names)
