"""The builder's probes for the ``nemotron_h`` family, on the chip, outside
any timed window (``--rehearse`` runs them tiny on the CPU).

    python3 tools/nemotron_h_controls.py readings --seeds 12 [--first-seed N]
    python3 tools/nemotron_h_controls.py controls --seeds 12 [--first-seed N]
        [--controls bf16_router_f32,bf16]

One process and one JSON line a seed, so every program compiles once for
all the seeds.  Both give the model the seed's weights as a benchmark run
builds it (amp O2, bf16) and the cell's first batch, and measure with
``benchmarks/lib/check.py``'s own functions against its ``tolerances``.
``lib/check.py`` has no function that decides: ``correct`` here is the line
``lib/train.py::_check`` ends on (every compared value under its limit).

``readings``: for every expert block, how many of the tokens' (token,
expert) assignments the program's router (float32 scores of bf16
activations) and the reference's (float32 throughout) choose differently,
how many of those fall on a held expert, and how many rows reached the
experts held here; then what a traced run compares (eval logits, the
step's loss, every gradient) against the reference, and the logits once
more against the reference GIVEN THE PROGRAM'S CHOICE of experts
(``logits_rel_rms_same_choice``: what is left of the reading when no
assignment differs).  ``TOLERANCES`` in ``benchmarks/families/
nemotron_h.py`` cite them.

``controls``: the reference in the program's place at a precision below
the one the configuration states, against the float32 reference, by the
comparisons and limits a traced run is held to: ``bf16`` has every tensor
and every sum in bf16 (the router's scores, the decays and the carried
state among them), ``bf16_router_f32`` keeps the router's weight, bias and
scores in float32 and nothing else.  ``logits_rel_rms_same_choice`` as
above.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CELL = "train_nemo3nano_s8192"


def setup(rehearse, cell=CELL):
    import jax
    if rehearse:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        sys.exit("controls: no TPU here; --rehearse runs tiny on the CPU")
    from benchmarks.lib import harness
    spec = harness.benchmark_spec()
    _, config, traffic = harness.load_cell(spec, cell, rehearse)
    return config, traffic


def first_batch(config, traffic, seed):
    """The cell's first batch of ``seed``, as ``lib/train.py`` draws it."""
    import jax.numpy as jnp
    from benchmarks.lib import seeds, train
    return train._make_batches(
        jnp.asarray(seeds.key_words(seed, "batches")), traffic["ring"],
        traffic["batch"], traffic["seq"],
        min(config["token_id_limit"],
            config["family"].vocab_size(config["model"])))[0]


def built(config, traffic, seed):
    """(model, weights, first batch) as ``lib/train.py`` makes them."""
    from benchmarks.lib import harness
    quiet = types.SimpleNamespace(
        args=types.SimpleNamespace(seed=seed), part=lambda name: None)
    model, weights = harness.build_model(quiet, config, traffic["amp"])
    return model, weights, first_batch(config, traffic, seed)


def reseeded(config, model, seed):
    """``model`` given the weights of ``seed`` in place: its state."""
    from benchmarks.lib import seeds, weights as weights_mod
    model.load_functional_state(weights_mod.make_weights(
        seeds.key_words(seed, "weights"), model.functional_state(),
        config["family"], config["model"]))
    return model.functional_state()


def walker(model_dict, dtype=None, router_dtype=None):
    """``(weights, ids, chosen=None) -> (float32 logits, {block: (b, s, k)
    expert ids})``: the reference in ``dtype`` (default float32), a block
    at a time.  An expert block takes ``chosen[block]`` for its choice of
    experts where that is given, its own where not; either way the choice
    it used comes back."""
    from benchmarks.reference import nemotron_h_ref as ref
    import jax
    dtype = dtype or ref.F32
    pattern = model_dict["hybrid_override_pattern"]
    types = dict(model=model_dict, dtype=dtype, router_dtype=router_dtype)
    run = {kind: jax.jit(functools.partial(ref.block, kind=kind, **types))
           for kind in set(pattern)}
    choose = jax.jit(functools.partial(ref.choice, **types))
    head = jax.jit(functools.partial(
        ref.head, eps=model_dict["layer_norm_epsilon"], dtype=dtype))

    def walk(weights, ids, chosen=None):
        x = ref.embed(weights["backbone.embeddings.weight"], ids, dtype)
        used = {}
        for i, kind in enumerate(pattern):
            w = ref.layer_weights(weights, i, kind)
            if kind == ref.EXPERTS:
                used[i] = chosen[i] if chosen else choose(x, w)
                x = run[kind](x, w, chosen=used[i])
            else:
                x = run[kind](x, w)
        return head(x, weights["backbone.norm_f.weight"],
                    weights["lm_head.weight"]), used
    return walk


def verdict(compared, finite=True):
    """As ``lib/train.py::_check`` decides ``within``."""
    return bool(finite and all(v < limit for v, limit in compared.values()))


def readings(args):
    """One line a seed: router flips and rows on the held experts a
    layer, and the three numbers a traced run compares (logits, first
    loss, per-tensor gradients) of the program against the reference."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from benchmarks.lib import check, train
    from benchmarks.reference import nemotron_h_ref as ref
    from paddle_tpu.jit import TrainStep, functional_call
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.nn.layer.experts import RoutedExperts
    config, traffic = setup(args.rehearse)
    family, model_dict = config["family"], config["model"]
    held = jnp.asarray(model_dict["held_experts"])
    k, scale = (model_dict["num_experts_per_tok"],
                model_dict["routed_scaling_factor"])
    walk = walker(model_dict)
    model, _, _ = built(config, traffic, args.first_seed)
    step = TrainStep(model, family.loss_fn(), paddle.optimizer.AdamW(
        parameters=model.parameters(), learning_rate=1e-4))
    params_like, _ = train._release_state(step)
    experts = {name: int(name.split(".")[2])
               for name, layer in model.named_sublayers()
               if isinstance(layer, RoutedExperts)}

    @jax.jit
    def program(state, ids):
        """(eval logits, the input of every expert layer) of the model."""
        seen = {}
        hooks = [layer.register_forward_pre_hook(
            lambda layer, inputs, name=name: seen.__setitem__(
                name, inputs[0]._array))
            for name, layer in model.named_sublayers() if name in experts]
        model.eval()
        out, _ = functional_call(model, state, paddle.Tensor(ids))
        for hook in hooks:
            hook.remove()
        return out.astype(jnp.float32), seen
    grads_of = jax.jit(step._grads_core)
    ref_grads_of = jax.jit(jax.grad(family.reference_loss(model_dict)),
                           donate_argnums=0)
    scope = fa.interpret_scope if args.rehearse else contextlib.nullcontext
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        weights = reseeded(config, model, seed)
        ids = first_batch(config, traffic, seed)
        with scope():
            logits, seen = program(weights, ids)
        # the program's choice: its router's float32 scores of the bf16
        # activations its expert layers were given
        with jax.default_matmul_precision("highest"):
            ours = {i: ref.route(seen[name].astype(ref.F32), {
                key: jnp.asarray(v).astype(ref.F32) for key, v in
                ref.layer_weights(weights, i, ref.EXPERTS).items()
                if key in ref.ROUTER}, k, scale)[0]
                for name, i in experts.items()}
        exact, theirs = walk(weights, ids)
        line = {"seed": seed, "tokens": int(ids.size), "layers": {}}
        for i in sorted(ours):
            same = (ours[i][..., :, None] == theirs[i][..., None, :]).any(-1)
            on_held = (ours[i][..., None] == held).any(-1)
            line["layers"][str(i)] = {
                "flipped": int(same.size - same.sum()),
                "tokens_with_a_flip": int((~same.all(-1)).sum()),
                "flipped_on_held": int((~same & on_held).sum()),
                "rows_on_held": int(on_held.sum())}
        line["logits_rel_rms"] = check.logits_errors(logits,
                                                     exact)["rel_rms"]
        ref_loss = float(family.loss_of_logits(exact, ids))
        del exact
        line["logits_rel_rms_same_choice"] = check.logits_errors(
            logits, walk(weights, ids, ours)[0])["rel_rms"]
        del logits, seen
        params = {name: jnp.array(weights[name], dtype=like.dtype)
                  for name, like in params_like.items()}
        with scope():
            loss, _, sys_grads = grads_of(params, step.buffers,
                                          jax.random.key(0), (ids, ids))
        line["loss_rel"] = abs(float(loss) - ref_loss) / abs(ref_loss)
        errors = check.grad_errors(sys_grads, ref_grads_of(params, ids))
        line.update(grad_rel_worst=errors["worst"],
                    worst_tensor=errors["tensor"],
                    grad_rel_median=errors["median"])
        line["assignments"] = int(ids.size) * k
        line["flipped"] = [l["flipped"] for l in line["layers"].values()]
        line["rows_on_held"] = [l["rows_on_held"]
                                for l in line["layers"].values()]
        print(json.dumps(line), flush=True)
        del weights, params, sys_grads


CONTROLS = {"bf16": {"router_dtype": None},
            "bf16_router_f32": {"router_dtype": "float32"}}


def controls(args):
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import check
    from benchmarks.reference import nemotron_h_ref as ref
    config, traffic = setup(args.rehearse)
    family, model_dict = config["family"], config["model"]
    tol = check.tolerances(family)
    model, _, _ = built(config, traffic, args.first_seed)
    exact_walk = walker(model_dict)
    exact_grad = jax.jit(jax.grad(family.reference_loss(model_dict)))
    loss_of = lambda logits, ids: float(family.loss_of_logits(logits, ids))
    rounded = {}
    for name in args.controls.split(","):
        types = dict(dtype=jnp.bfloat16, **CONTROLS[name])
        rounded[name] = (walker(model_dict, **types), jax.jit(jax.grad(
            functools.partial(ref.loss, model=model_dict, **types))))
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        weights = reseeded(config, model, seed)
        ids = first_batch(config, traffic, seed)
        exact, _ = exact_walk(weights, ids)
        exact_loss = loss_of(exact, ids)
        lines = {}
        for name, (walk, _) in rounded.items():
            logits, choice = walk(weights, ids)
            errors = check.logits_errors(logits, exact)
            lines[name] = {
                "control": "reference_at_" + name, "seed": seed,
                "finite": errors["finite"], "compared": {
                    "loss_rel": [abs(loss_of(logits, ids) - exact_loss)
                                 / abs(exact_loss), tol["loss_rel"]],
                    "logits_rel_rms": [errors["rel_rms"],
                                       tol["logits_rel_rms"]]},
                "logits_rel_rms_same_choice": check.logits_errors(
                    logits, exact_walk(weights, ids, choice)[0])["rel_rms"]}
        del exact, logits
        trainable = {k: jnp.asarray(v, ref.F32) for k, v in weights.items()}
        exact_grads = exact_grad(trainable, ids)
        for name, (_, grad) in rounded.items():
            grads = check.grad_errors(grad(trainable, ids), exact_grads)
            line = lines[name]
            line["compared"]["grad_rel_worst"] = [grads["worst"],
                                                  tol["grad_rel"]]
            line.update(worst_tensor=grads["tensor"],
                        grad_median=grads["median"],
                        correct=verdict(line["compared"], line["finite"]))
            print(json.dumps(line), flush=True)
        del weights, trainable, exact_grads


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python3 tools/nemotron_h_controls.py")
    ap.add_argument("what", choices=("readings", "controls"))
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--controls", default="bf16_router_f32,bf16")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    {"readings": readings, "controls": controls}[args.what](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
