"""The ``nemotron_h`` family: everything the harness knows of a hybrid of
Mamba-2, routed-expert and attention blocks (NVIDIA Nemotron 3 Nano).

The model comes from the program (``paddle_tpu.models.nemotron_h``), the
reference from ``benchmarks/reference/nemotron_h_ref.py``, which imports
nothing of the program.  ``benchmarks/README.md``, "A model family", fixes
the names a family provides; this one departs from that list in two places:

* its layers carry roles of their own (``ssm``, ``ssm_scan``, ``moe``,
  ``moe_experts``; no ``mlp``), read by ``layer_metrics/ssm_ms.train.py``
  and its neighbours;
* the operations and bytes of its two new kernels (the scan, the grouped
  products) are in ``benchmarks/lib/flops_nemotron_h.py``, not in
  ``lib/flops.py``, which holds the flash kernel's alone.

``NEMOTRON_H_REFERENCE_CONTROL`` in the environment breaks the REFERENCE (a
run must then read ``correct: false``): ``no_experts`` drops the expert
blocks from it, ``no_carried_state`` makes its scan forget the state between
chunks.  It is the only environment knob of any family and stands in for a
seam the harness lacks (PERF.md section 7); the other controls, the
reference in the program's place at bf16 with and without a float32 router,
are ``tools/nemotron_h_controls.py controls``.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from benchmarks.lib import flops_nemotron_h as shapes
from benchmarks.reference import nemotron_h_ref as ref

#: the keys of a configuration file that hold the model dict (the fields of
#: ``NemotronHConfig`` as run) and its overrides for a rehearsal
MODEL_KEY = "nemotron_h_config"
REHEARSE_KEY = "rehearse_nemotron_h_config"

#: Limits of the comparison that decides ``correct``, by the names of
#: ``lib/check.py::tolerances``, with their measured basis: one TPU v5e,
#: 1 x 8,192 tokens, the program in bf16 with its float32 islands, the
#: reference in float32 (my chip runs, PR 33; ``tools/
#: nemotron_h_controls.py readings`` and ``controls``).  A router decides by
#: a comparison: where two experts' scores lie closer than the rounding of
#: the bf16 activations in front of the router, program and reference pick
#: differently, and that token's output moves by a whole expert, which no
#: rounding bound covers.  The harness compares whole tensors, so these
#: flips, not the matrix products' rounding, set every reading below: given
#: the program's own choice of experts the reference stands 0.012 from it
#: (``same_choice``; GPT-2's block reads 0.012 too), left to its own 0.033;
#: the program's worst gradient is a router's weight in every reading, a
#: sum over the tokens sent to held experts, of which 1-3% differ.  What
#: the limits can and cannot tell, then: a program with no float32 router
#: (``bf16``) is told on every seed; one that keeps the router alone
#: (``bf16_router_f32``) reads 1.1-1.75 times the program on the same seed
#: (logits) and is told by the logits limit on 12 seeds of 13 (the
#: gradient limit adds none) -- a run at a time; a driver's check makes
#: fourteen runs a cell on seeds of its own and one ``false`` refuses.  A
#: comparison that is blind to the flips needs a seam in ``lib/train.py``
#: (PERF.md section 7); with it the limit would stand at 0.015.
BASIS = {
    "router_flips_of_49152_assignments_a_layer_21_seeds": {
        "block_1": [264, 309], "block_3": [446, 526], "block_6": [591, 788],
        "block_8": [732, 947], "of_them_on_a_held_expert": [14, 89],
        "tokens_with_a_flip_block_8": [701, 871],
    },
    "logits_rel_rms": {
        "chip_program_21_seeds_of_the_probe": [0.02738, 0.03670],
        "chip_program_27_runs_of_the_cell": [0.03065, 0.03670],
        "chip_program_mean_and_deviation_of_42": [0.03330, 0.00216],
        "chip_program_widest_ever": 0.03826,    # a run of the first path
        "chip_reference_at_bf16_router_float32_13_seeds": [0.04048, 0.05268],
        "its_first_reading_seed_2147485000": 0.04442,
        "its_mean_and_deviation": [0.04796, 0.00339],
        "over_the_program_on_the_same_seed_12_seeds": [1.10, 1.75],
        "chip_reference_at_bf16_6_seeds": [0.05298, 0.06063],
        "chip_reference_without_experts": 1.2147,
        "chip_reference_forgetting_its_state": 0.1460,
        "same_choice": {
            "chip_program_9_seeds": [0.01161, 0.01233],
            "chip_reference_at_bf16_router_float32_12_seeds":
                [0.01821, 0.02452],
            "chip_reference_at_bf16_4_seeds": [0.01868, 0.02164],
        },
        "limit": "0.042: 14% above the widest of the 42 readings of the "
                 "paths kept (4.0 deviations above their mean), 10% above "
                 "the widest ever; of the float32-router control's 13 "
                 "seeds one reads under it (0.0405)",
    },
    "grad_rel": {
        "chip_program_worst_tensor_25_readings": [0.2097, 0.2940],
        "their_mean_and_deviation": [0.2595, 0.0216],
        "the_tensor": "a router's weight (gate.weight of block 6 or 8) in "
                      "every reading; the median tensor reads 0.039-0.052",
        "chip_reference_at_bf16_router_float32_13_seeds": [0.2952, 0.3539],
        "its_first_reading_seed_2147485000": 0.3063,
        "chip_reference_at_bf16_6_seeds": [0.3461, 0.3914],
        "chip_reference_without_experts": 1.98e30,
        "chip_reference_forgetting_its_state": 1.0762,
        "limit": "0.32: 9% above the program's widest, under every "
                 "reading of the bf16 control and 8 of the 13 of the "
                 "float32-router one; it does not separate that control "
                 "(flips at the router's weight, on both sides)",
    },
    "loss_rel": {
        "chip_program_widest_of_30": 9.6e-5,
        "chip_reference_at_bf16_router_float32_12_seeds": [2.7e-5, 1.0e-4],
        "chip_reference_at_bf16_6_seeds": [3.3e-6, 1.6e-4],
        "note": "does not discriminate at random initialisation (the gpt "
                "family's note holds); the harness's accepted limit leaves "
                "twenty times of room",
    },
}
TOLERANCES = {"logits_rel_rms": 0.042, "loss_rel": 2e-3, "grad_rel": 0.32}


def _control() -> dict:
    how = os.environ.get("NEMOTRON_H_REFERENCE_CONTROL", "")
    if how not in ("", "no_experts", "no_carried_state"):
        raise SystemExit("benchmark: NEMOTRON_H_REFERENCE_CONTROL=%r is "
                         "neither no_experts nor no_carried_state" % how)
    return {"with_experts": how != "no_experts",
            "carry_state": how != "no_carried_state"}


# -- the program's side ---------------------------------------------------------

def build_model(model: dict):
    """The program's model from the model dict (no dropout anywhere)."""
    from paddle_tpu.models.nemotron_h import (NemotronHConfig,
                                              NemotronHForCausalLM)
    return NemotronHForCausalLM(NemotronHConfig(**model))


def loss_fn():
    """``(logits, labels) -> loss``, the loss the step trains with."""
    from paddle_tpu.models.nemotron_h import NemotronHPretrainingCriterion
    crit = NemotronHPretrainingCriterion()
    return lambda logits, labels: crit(logits, labels)


def vocab_size(model: dict) -> int:
    return model["vocab_size"]


# -- initialisation -------------------------------------------------------------

def _inv_softplus(y: float) -> float:
    return math.log(math.expm1(y))


def init_of(name: str, model: dict):
    """The published scheme as far as the source states one (``assumed`` in
    the configuration file): N(0, initializer_range) for embeddings and
    matrices, divided by sqrt(layers) for the projections that write into
    the residual stream (``rescale_prenorm_residual``: the mixers'
    ``out_proj`` / ``o_proj`` and the experts' ``down_proj``), ``A_log``
    uniform in [log 1, log 16], ``dt_bias`` uniform in softplus^-1 of
    [time_step_min, time_step_max], ``D`` and norm gains 1, the
    convolution's taps uniform in +-1/sqrt(taps) with a zero bias, the
    router's selection bias 0."""
    std, layers = model["initializer_range"], len(
        model["hybrid_override_pattern"])
    if name.endswith(("norm.weight", "norm_weight", "norm_f.weight",
                      "mixer.D")):
        return ("constant", 1.0)
    if name.endswith(("conv1d_bias", "e_score_correction_bias")):
        return ("constant", 0.0)
    if name.endswith("mixer.A_log"):
        return ("uniform", (0.0, math.log(16.0)))
    if name.endswith("mixer.dt_bias"):
        return ("uniform", (_inv_softplus(model["time_step_min"]),
                            _inv_softplus(model["time_step_max"])))
    if name.endswith("conv1d_weight"):
        bound = 1.0 / math.sqrt(model["conv_kernel"])
        return ("uniform", (-bound, bound))
    if name.endswith(("out_proj.weight", "o_proj.weight", "down_proj",
                      "down_proj.weight")):
        return ("normal", std / math.sqrt(layers))
    return ("normal", std)


# -- the plain reference --------------------------------------------------------

def reference_forward(model: dict):
    """``(weights, ids) -> float32 logits``, the reference run one block at
    a time: one small program a kind of block, called once a layer, with
    the weights as arguments."""
    pattern, flags = model["hybrid_override_pattern"], _control()
    embed = jax.jit(ref.embed)
    blocks = {kind: jax.jit(functools.partial(
        ref.block, kind=kind, model=model, **flags)) for kind in set(pattern)}
    head = jax.jit(functools.partial(ref.head,
                                     eps=model["layer_norm_epsilon"]))

    def forward(weights, ids):
        x = embed(weights["backbone.embeddings.weight"], ids)
        for i, kind in enumerate(pattern):
            x = blocks[kind](x, ref.layer_weights(weights, i, kind))
        return head(x, weights["backbone.norm_f.weight"],
                    weights["lm_head.weight"])
    return forward


def loss_of_logits(logits, ids):
    """The reference's training loss of float32 ``logits`` (b, s, V)."""
    return jnp.mean(ref.token_losses(logits, ids))


def reference_loss(model: dict):
    """``(weights, ids) -> scalar`` that ``jax.grad`` takes: a block is a
    ``jax.checkpoint``, attention scores and the scan go in blocks of the
    row."""
    return functools.partial(ref.loss, model=model, **_control())


# -- operations from shapes -----------------------------------------------------

def train_flops_per_token(model: dict, seq: int) -> float:
    """Training FLOPs one token needs AS RUN on this chip, forward and
    backward, no recompute: 6 x (matmul parameters a token touches) plus
    what grows with the row.

    Matmul parameters a token, Nemotron 3 Nano as cut (hidden 2,688):
      Mamba-2 block   in 2,688 x 10,304 + out 4,096 x 2,688 = 38,707,200
      attention block qkv 2,688 x 4,608 + o 4,096 x 2,688   = 23,396,352
      expert block    router 2,688 x 128 + shared 2 x 2,688 x 3,712
                      + 6 x 8/128 of one expert (2 x 2,688 x 1,856: the
                      expected share of a token's 6 experts that is held
                      here)                                  = 24,041,472
      head            2,688 x 16,384                         = 44,040,192
    4 x 38,707,200 + 23,396,352 + 4 x 24,041,472 + 44,040,192
    = 318,431,232 -> x 6 = 1.9106 GFLOP.
    Causal attention: 6 x s x heads x head_dim a layer (as the gpt
    family's 6 L s h) = 6 x 8,192 x 4,096 = 0.2013 GFLOP.
    The scan (``flops_nemotron_h.scan_flops``, forward x 3): a token of a
    chunk of L = 128 costs G L N + H L P (its row of ``C.B^T`` and of the
    decayed product with x, causal halves) + 4 H P N (its share of the
    chunk's state, made and read) = 131,072 + 524,288 + 2,097,152 =
    2,752,512 FLOPs a layer forward; x 3 x 4 layers = 0.0330 GFLOP.
    Sum at s = 8,192: 2.1449 GFLOP a token (17.57 TFLOP a step of 8,192
    tokens, 89.2 ms at 197 TFLOP/s)."""
    pattern = model["hybrid_override_pattern"]
    h = model["hidden_size"]
    d_inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    conv_dim = d_inner + 2 * model["n_groups"] * model["ssm_state_size"]
    heads, kv, d = (model["num_attention_heads"],
                    model["num_key_value_heads"], model["head_dim"])
    per_kind = {
        ref.MAMBA: h * (d_inner + conv_dim + model["mamba_num_heads"])
        + d_inner * h,
        ref.ATTENTION: h * (heads + 2 * kv) * d + heads * d * h,
        ref.EXPERTS: h * model["router_width"]
        + 2 * h * model["moe_shared_expert_intermediate_size"]
        + (model["num_experts_per_tok"] * model["n_routed_experts"]
           / model["router_width"]) * 2 * h * model["moe_intermediate_size"],
    }
    matmul_params = sum(per_kind[kind] for kind in pattern) \
        + h * model["vocab_size"]
    attention = 6.0 * pattern.count(ref.ATTENTION) * seq * heads * d
    scan = pattern.count(ref.MAMBA) * shapes.scan_flops(
        1, seq, model)["total"] / seq
    return 6.0 * matmul_params + attention + scan


def flash_calls(model: dict) -> list:
    """The attention blocks call the causal flash kernel, with the
    key/value group expanded in front of it: all the query heads."""
    return [{"layers": model["hybrid_override_pattern"].count(ref.ATTENTION),
             "heads": model["num_attention_heads"],
             "head_dim": model["head_dim"]}]


# -- published against as run ---------------------------------------------------

WIDTHS = ("hidden_size", "mamba_num_heads", "mamba_head_dim",
          "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
          "num_attention_heads", "num_key_value_heads", "head_dim",
          "moe_intermediate_size", "moe_shared_expert_intermediate_size",
          "num_experts_per_tok", "routed_scaling_factor",
          "layer_norm_epsilon", "time_step_min", "time_step_max")


def width_pairs(config: dict) -> list:
    """``(name, as run, as published)`` for every size the source states:
    a test holds each pair equal unless ``reduced`` names it.  The model
    dict, the file's top level (the catalog's keys, as run) and
    ``published`` are all three held together."""
    m, p = config[MODEL_KEY], config["published"]
    pairs = [(k, m[k], p[k]) for k in WIDTHS]
    pairs += [("router_width", m["router_width"], p["n_routed_experts"]),
              ("n_routed_experts", m["n_routed_experts"],
               p["n_routed_experts"]),
              ("vocab_size", m["vocab_size"], p["vocab_size"]),
              ("hybrid_override_pattern", m["hybrid_override_pattern"],
               p["hybrid_override_pattern"]),
              ("num_hidden_layers", len(m["hybrid_override_pattern"]),
               p["num_hidden_layers"]),
              ("initializer_range", m["initializer_range"],
               config["assumed_sizes"]["initializer_range"])]
    # the file's top level repeats the source's keys as run
    pairs += [("top_level." + k, config[k], m[k]) for k in WIDTHS
              + ("n_routed_experts", "vocab_size",
                 "hybrid_override_pattern") if k in p]
    pairs.append(("top_level.num_hidden_layers", config["num_hidden_layers"],
                  len(m["hybrid_override_pattern"])))
    return pairs
