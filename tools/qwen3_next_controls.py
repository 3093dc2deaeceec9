"""The builder's probes for the ``qwen3_next`` family, on the chip, outside
any timed window (``--rehearse`` runs them tiny on the CPU); the family's
``BASIS`` cites them.  ``tools/nemotron_h_controls.py`` is the same pair for
``nemotron_h`` and lends its helpers.

    python3 tools/qwen3_next_controls.py readings --seeds 12 [--first-seed N]
        [--gradient-seeds 3] [--same-choice-seeds 4]
    python3 tools/qwen3_next_controls.py controls --seeds 4 [--first-seed N]
        [--controls bf16_router_f32,bf16,no_experts,...] [--gradient-seeds 2]

One process and one JSON line a seed, so every program compiles once for
all the seeds.

``readings``: for every expert layer, how many of the tokens' (token,
expert) assignments the program's router (float32 probabilities of bf16
activations) and the reference's (float32 throughout) choose differently
and how many of those fall on a held expert; then what a traced run
compares (eval logits; on the first ``--gradient-seeds`` seeds the step's
loss and every gradient too) against the reference, and on the first
``--same-choice-seeds`` seeds the logits once more against the reference
GIVEN THE PROGRAM'S CHOICE of experts (``logits_rel_rms_same_choice``).

``controls``: the reference in the program's place at a precision below the
one the configuration states, against the float32 reference, by the
comparisons and limits a traced run is held to: ``bf16`` has every tensor
and every sum in bf16 (the router's probabilities, the decays and the
carried state among them), ``bf16_router_f32`` keeps the router's weight
and probabilities in float32 and nothing else; ``no_experts``,
``no_carried_state`` and ``no_correction`` are the float32 reference broken
(the family's ``QWEN3_NEXT_REFERENCE_CONTROL`` does the same to a whole
run).  Gradients on the first ``--gradient-seeds`` seeds.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from tools.nemotron_h_controls import (built, first_batch,  # noqa: E402
                                       reseeded, setup, verdict)

CELL = "train_qwen3next_s16384"


def walker(model_dict, dtype=None, router_dtype=None, **flags):
    """``(weights, ids, chosen=None) -> (float32 logits, {layer: (b, s, k)
    expert ids})``: the reference in ``dtype`` (default float32), a layer
    at a time.  A layer's experts take ``chosen[layer]`` for their choice
    where that is given, their own where not; either way the choice used
    comes back."""
    from benchmarks.reference import qwen3_next_ref as ref
    import jax
    dtype = dtype or ref.F32
    kinds = ref.layer_types(model_dict)
    types = dict(model=model_dict, dtype=dtype, router_dtype=router_dtype)
    run = {kind: jax.jit(functools.partial(ref.layer, kind=kind, **types,
                                           **flags))
           for kind in set(kinds)}
    choose = {kind: jax.jit(functools.partial(ref.choice, kind=kind, **types))
              for kind in set(kinds)}
    head = jax.jit(functools.partial(
        ref.head, eps=model_dict["rms_norm_eps"], dtype=dtype))

    def walk(weights, ids, chosen=None):
        x = ref.embed(weights["model.embed_tokens.weight"], ids, dtype)
        used = {}
        for i, kind in enumerate(kinds):
            w = ref.layer_weights(weights, i, kind)
            used[i] = chosen[i] if chosen else choose[kind](x, w)
            x = run[kind](x, w, chosen=used[i])
        return head(x, weights["model.norm.weight"],
                    weights["lm_head.weight"]), used
    return walk


def readings(args):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from benchmarks.lib import check, train
    from benchmarks.reference import qwen3_next_ref as ref
    from paddle_tpu.jit import TrainStep, functional_call
    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.nn.layer.experts import RoutedExperts
    config, traffic = setup(args.rehearse, CELL)
    family, model_dict = config["family"], config["model"]
    held = jnp.asarray(model_dict["held_experts"])
    k = model_dict["num_experts_per_tok"]
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

    @jax.jit
    def choice_of(u, router):
        with jax.default_matmul_precision("highest"):
            return ref.route(u.astype(ref.F32),
                             {"mlp.gate.weight": router.astype(ref.F32)},
                             k)[0]
    grads_of = jax.jit(step._grads_core)
    ref_grads_of = jax.jit(jax.grad(family.reference_loss(model_dict)),
                           donate_argnums=0)
    scope = fa.interpret_scope if args.rehearse else contextlib.nullcontext
    for n, seed in enumerate(range(args.first_seed,
                                   args.first_seed + args.seeds)):
        weights = reseeded(config, model, seed)
        ids = first_batch(config, traffic, seed)
        with scope():
            logits, seen = program(weights, ids)
        # the program's choice: its router's float32 probabilities of the
        # bf16 activations its expert layers were given
        ours = {i: choice_of(seen[name],
                             weights["model.layers.%d.mlp.gate.weight" % i])
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
        if n < args.same_choice_seeds:
            line["logits_rel_rms_same_choice"] = check.logits_errors(
                logits, walk(weights, ids, ours)[0])["rel_rms"]
        del logits, seen
        if n < args.gradient_seeds:
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
            del params, sys_grads
        line["assignments"] = int(ids.size) * k
        print(json.dumps(line), flush=True)
        del weights


CONTROLS = {"bf16": {"dtype": "bfloat16"},
            "bf16_router_f32": {"dtype": "bfloat16",
                                "router_dtype": "float32"},
            "no_experts": {"with_experts": False},
            "no_carried_state": {"carry_state": False},
            "no_correction": {"correction": False}}


def controls(args):
    import jax
    import jax.numpy as jnp
    from benchmarks.lib import check
    from benchmarks.reference import qwen3_next_ref as ref
    config, traffic = setup(args.rehearse, CELL)
    family, model_dict = config["family"], config["model"]
    tol = check.tolerances(family)
    model, _, _ = built(config, traffic, args.first_seed)
    exact_walk = walker(model_dict)
    exact_grad = jax.jit(jax.grad(family.reference_loss(model_dict)))
    loss_of = lambda logits, ids: float(family.loss_of_logits(logits, ids))
    rounded = {}
    for name in args.controls.split(","):
        how = CONTROLS[name]
        rounded[name] = (walker(model_dict, **how), jax.jit(jax.grad(
            functools.partial(ref.loss, model=model_dict, **how))))
    for n, seed in enumerate(range(args.first_seed,
                                   args.first_seed + args.seeds)):
        with_gradients = n < args.gradient_seeds
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
                }
            if "dtype" in CONTROLS[name] and n < args.same_choice_seeds:
                lines[name]["logits_rel_rms_same_choice"] = \
                    check.logits_errors(logits, exact_walk(
                        weights, ids, choice)[0])["rel_rms"]
        del exact, logits
        if with_gradients:
            trainable = {k: jnp.asarray(v, ref.F32)
                         for k, v in weights.items()}
            exact_grads = exact_grad(trainable, ids)
        for name, (_, grad) in rounded.items():
            line = lines[name]
            if with_gradients:
                grads = check.grad_errors(grad(trainable, ids), exact_grads)
                line["compared"]["grad_rel_worst"] = [grads["worst"],
                                                      tol["grad_rel"]]
                line.update(worst_tensor=grads["tensor"],
                            grad_median=grads["median"])
            line["correct"] = verdict(line["compared"], line["finite"])
            print(json.dumps(line), flush=True)
        del weights


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python3 tools/qwen3_next_controls.py")
    ap.add_argument("what", choices=("readings", "controls"))
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--controls", default="bf16_router_f32,bf16")
    ap.add_argument("--gradient-seeds", type=int, default=2,
                    help="gradients too on the first N seeds (a traced "
                         "run's part); logits and loss alone on the rest")
    ap.add_argument("--same-choice-seeds", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    {"readings": readings, "controls": controls}[args.what](args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
