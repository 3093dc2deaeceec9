"""The ``qwen3_next`` family: everything the harness knows of a hybrid of
Gated DeltaNet linear-attention layers and output-gated attention, every
layer with a routed expert layer (Qwen3-Next).

The model comes from the program (``paddle_tpu.models.qwen3_next``), the
reference from ``benchmarks/reference/qwen3_next_ref.py``, which imports
nothing of the program.  ``benchmarks/README.md``, "A model family", fixes
the names a family provides; as ``nemotron_h`` this one departs from that
list in two places:

* its layers carry roles of their own (``linear_attn``, ``linear_attn_scan``,
  ``moe``, ``moe_experts``; no ``mlp``), read by ``layer_metrics/
  linear_attn_ms.train.py`` and its neighbours;
* the operations and bytes of its kernels' work (the delta rule, the nine
  grouped products of a gated expert layer) are in ``benchmarks/lib/
  flops_qwen3_next.py``, not in ``lib/flops.py``, which holds the flash
  kernel's alone.

``QWEN3_NEXT_REFERENCE_CONTROL`` in the environment breaks the REFERENCE (a
run must then read ``correct: false``): ``no_experts`` drops the expert
layers from it, ``no_carried_state`` makes its delta rule forget the state
between blocks of the row, ``no_correction`` drops the rule's correction
term ``S^T k_t``.  As ``NEMOTRON_H_REFERENCE_CONTROL`` it stands in for a
seam the harness lacks (PERF.md section 7); the other controls, the
reference in the program's place at bf16 with and without a float32 router,
are ``tools/qwen3_next_controls.py controls``.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from benchmarks.lib import flops_qwen3_next as shapes
from benchmarks.reference import qwen3_next_ref as ref

#: the keys of a configuration file that hold the model dict (the fields of
#: ``Qwen3NextConfig`` as run) and its overrides for a rehearsal
MODEL_KEY = "qwen3_next_config"
REHEARSE_KEY = "rehearse_qwen3_next_config"

#: Limits of the comparison that decides ``correct``, by the names of
#: ``lib/check.py::tolerances``, with their measured basis: one TPU v5e,
#: 1 x 16,384 tokens, the program in bf16 with its float32 islands, the
#: reference in float32 (my chip runs, PR 35; ``tools/
#: qwen3_next_controls.py readings`` and ``controls``, and the cell's own
#: runs).  As in ``nemotron_h`` a router decides by a comparison and the
#: harness compares whole tensors, but here a flipped choice lands on one of
#: the 32 held experts for 1 token in 16 only, so the flips move the logits'
#: reading by 7% (``same_choice``), not by two thirds: what is compared is
#: the rounding of the delta rule, the kernels and the products.  The
#: logits limit lies between the program's widest reading and the least of
#: the reference computed in bf16 (the nearest precision below the one the
#: configuration states), which it tells on every seed read; the worst
#: gradient is a router's weight in every reading (a sum over the tokens
#: sent to held experts, of which a few differ on either side), its limit
#: stands a quarter above the program's widest and does not separate that
#: control (as in ``nemotron_h``); the loss keeps the harness's accepted
#: limit, two orders above every reading.
BASIS = {
    "router_flips_of_163840_assignments_a_layer_12_seeds": {
        "layer_0": [1505, 1622], "layer_1": [2740, 2928],
        "layer_2": [3932, 4267], "layer_3": [3966, 4283],
        "of_them_on_a_held_expert": [81, 295],
        "rows_on_the_held_experts_a_layer": [8789, 11516],
        "uniform_routing_sends": 10240,
    },
    "logits_rel_rms": {
        "chip_program_12_seeds_of_the_probe": [0.02049, 0.02120],
        "chip_program_10_runs_of_the_cell": [0.02049, 0.02116],
        "chip_reference_at_bf16_4_seeds": [0.03055, 0.03592],
        "chip_reference_at_bf16_router_float32_4_seeds": [0.03043, 0.03583],
        "chip_reference_without_experts": 0.4720,
        "chip_reference_forgetting_its_state": 0.7054,
        "chip_reference_without_the_correction_term": 0.7848,
        "same_choice": {
            "chip_program_4_seeds": [0.01923, 0.01964],
            "chip_reference_at_bf16_2_seeds": [0.03184, 0.03442],
        },
        "limit": "0.026: 23% above the widest of the program's 22 "
                 "readings (which lie within 4% of each other), 15% under "
                 "the least of the bf16 reference's 8",
    },
    "grad_rel": {
        "chip_program_worst_tensor_7_readings": [0.1748, 0.2002],
        "the_tensor": "a router's weight (mlp.gate.weight of layer 2 or 3) "
                      "in every reading; the median tensor reads 0.033",
        "chip_reference_at_bf16_2_seeds": [0.2289, 0.2365],
        "chip_reference_at_bf16_router_float32_2_seeds": [0.2214, 0.2404],
        "limit": "0.25: a quarter above the program's widest; over every "
                 "reading of the bf16 controls, which the logits limit "
                 "tells, not this one",
    },
    "loss_rel": {
        "chip_program_widest_of_13": 2.7e-5,
        "chip_reference_at_bf16_8_readings": [7.8e-6, 3.5e-5],
        "broken_references": [4.1e-4, 1.0e-3],
        "note": "does not discriminate at random initialisation (the gpt "
                "family's note holds); the harness's accepted limit leaves "
                "seventy times of room",
    },
}
TOLERANCES = {"logits_rel_rms": 0.026, "loss_rel": 2e-3, "grad_rel": 0.25}

CONTROLS = ("", "no_experts", "no_carried_state", "no_correction")


def _control() -> dict:
    how = os.environ.get("QWEN3_NEXT_REFERENCE_CONTROL", "")
    if how not in CONTROLS:
        raise SystemExit("benchmark: QWEN3_NEXT_REFERENCE_CONTROL=%r is none "
                         "of %r" % (how, CONTROLS[1:]))
    return {"with_experts": how != "no_experts",
            "carry_state": how != "no_carried_state",
            "correction": how != "no_correction"}


# -- the program's side ---------------------------------------------------------

def build_model(model: dict):
    """The program's model from the model dict (no dropout anywhere)."""
    from paddle_tpu.models.qwen3_next import (Qwen3NextConfig,
                                              Qwen3NextForCausalLM)
    return Qwen3NextForCausalLM(Qwen3NextConfig(**model))


def loss_fn():
    """``(logits, labels) -> loss``, the loss the step trains with."""
    from paddle_tpu.models.qwen3_next import Qwen3NextPretrainingCriterion
    crit = Qwen3NextPretrainingCriterion()
    return lambda logits, labels: crit(logits, labels)


def vocab_size(model: dict) -> int:
    return model["vocab_size"]


# -- initialisation -------------------------------------------------------------

def _inv_softplus(y: float) -> float:
    return math.log(math.expm1(y))


def init_of(name: str, model: dict):
    """The published scheme as far as the source states one (``assumed`` in
    the configuration file): N(0, initializer_range) for embeddings,
    matrices, the router and the shared expert's gate, divided by
    sqrt(layers) for the projections that write into the residual stream
    (the mixers' ``out_proj`` / ``o_proj`` and every ``down_proj``); the
    zero-centred norm gains 0 (a gain of one) and the gated norm's plain
    gain 1; ``A_log`` uniform in [log 1, log 16], ``dt_bias`` uniform in
    softplus^-1 of [time_step_min, time_step_max], the convolution's taps
    uniform in +-1/2."""
    std, layers = model["initializer_range"], model["num_hidden_layers"]
    if name.endswith("linear_attn.norm_weight"):
        return ("constant", 1.0)
    if name.endswith(("layernorm.weight", "_norm.weight", "model.norm.weight")):
        return ("constant", 0.0)
    if name.endswith("linear_attn.A_log"):
        return ("uniform", (0.0, math.log(16.0)))
    if name.endswith("linear_attn.dt_bias"):
        return ("uniform", (_inv_softplus(model["time_step_min"]),
                            _inv_softplus(model["time_step_max"])))
    if name.endswith("conv1d_weight"):
        return ("uniform", (-0.5, 0.5))
    if name.endswith(("out_proj.weight", "o_proj.weight", "down_proj",
                      "down_proj.weight")):
        return ("normal", std / math.sqrt(layers))
    return ("normal", std)


# -- the plain reference --------------------------------------------------------

def reference_forward(model: dict):
    """``(weights, ids) -> float32 logits``, the reference run one layer at
    a time: one small program a kind of layer, called once a layer, with
    the weights as arguments."""
    kinds, flags = ref.layer_types(model), _control()
    embed = jax.jit(ref.embed)
    layers = {kind: jax.jit(functools.partial(
        ref.layer, kind=kind, model=model, **flags)) for kind in set(kinds)}
    head = jax.jit(functools.partial(ref.head, eps=model["rms_norm_eps"]))

    def forward(weights, ids):
        x = embed(weights["model.embed_tokens.weight"], ids)
        for i, kind in enumerate(kinds):
            x = layers[kind](x, ref.layer_weights(weights, i, kind))
        return head(x, weights["model.norm.weight"],
                    weights["lm_head.weight"])
    return forward


def loss_of_logits(logits, ids):
    """The reference's training loss of float32 ``logits`` (b, s, V)."""
    return jnp.mean(ref.token_losses(logits, ids))


def reference_loss(model: dict):
    """``(weights, ids) -> scalar`` that ``jax.grad`` takes: a layer is a
    ``jax.checkpoint``, attention goes in blocks of query rows and keys,
    the delta rule in blocks of the row, an expert at a time."""
    return functools.partial(ref.loss, model=model, **_control())


# -- operations from shapes -----------------------------------------------------

def train_flops_per_token(model: dict, seq: int) -> float:
    """Training FLOPs one token needs AS RUN on this chip, forward and
    backward, no recompute: 6 x (matmul parameters a token touches) plus
    what grows with the row or the chunk.

    Matmul parameters a token, Qwen3-Next as cut (hidden 2,048):
      Gated DeltaNet  in 2,048 x (12,288 + 64) + out 4,096 x 2,048
                                                             = 33,685,504
      gated attention q 2,048 x 8,192 + k, v 2 x 2,048 x 512
                      + o 4,096 x 2,048                      = 27,262,976
      expert layer    router 2,048 x 512 + shared 3 x 2,048 x 512 + its
                      gate 2,048 + 10 x 32/512 of one expert (3 x 2,048 x
                      512: the expected share of a token's 10 experts
                      that is held here)                     =  6,162,432
      head            2,048 x 19,072                         = 39,059,456
    3 x 33,685,504 + 27,262,976 + 4 x 6,162,432 + 39,059,456
    = 192,028,672 -> x 6 = 1.1522 GFLOP.
    Causal attention: 6 x s x heads x head_dim a layer (as the gpt
    family's 6 L s h) = 6 x 16,384 x 4,096 = 0.4027 GFLOP.
    The delta rule (``flops_qwen3_next.delta_rule_flops``, forward x 3): a
    chunk of 64 tokens costs 33,554,432 (``K K^T`` and ``Q K^T``, 16 key
    heads) + 100,663,296 (three (C, C) products, 32 value heads) +
    201,326,592 (three state products) + 5,592,405 (the substitution) =
    341,136,725 FLOPs forward, 5,330,261 a token; x 3 x 3 layers = 0.0480
    GFLOP.
    Sum at s = 16,384: 1.6028 GFLOP a token (26.26 TFLOP a step of 16,384
    tokens, 133.3 ms at 197 TFLOP/s)."""
    kinds = ref.layer_types(model)
    h = model["hidden_size"]
    key_dim = model["linear_num_key_heads"] * model["linear_key_head_dim"]
    value_dim = (model["linear_num_value_heads"]
                 * model["linear_value_head_dim"])
    heads, kv, d = (model["num_attention_heads"],
                    model["num_key_value_heads"], model["head_dim"])
    width = model["moe_intermediate_size"]
    per_kind = {
        ref.LINEAR: h * (2 * key_dim + 2 * value_dim
                         + 2 * model["linear_num_value_heads"])
        + value_dim * h,
        ref.FULL: h * (2 * heads + 2 * kv) * d + heads * d * h,
    }
    experts = (h * model["router_width"]
               + 3 * h * model["shared_expert_intermediate_size"] + h
               + (model["num_experts_per_tok"] * model["num_experts"]
                  / model["router_width"]) * 3 * h * width)
    matmul_params = sum(per_kind[kind] + experts for kind in kinds) \
        + h * model["vocab_size"]
    attention = 6.0 * kinds.count(ref.FULL) * seq * heads * d
    rule = kinds.count(ref.LINEAR) * shapes.delta_rule_flops(
        1, seq, model)["total"] / seq
    return 6.0 * matmul_params + attention + rule


def flash_calls(model: dict) -> list:
    """The full-attention layers call the causal flash kernel, with the
    key/value group expanded in front of it: all the query heads."""
    return [{"layers": ref.layer_types(model).count(ref.FULL),
             "heads": model["num_attention_heads"],
             "head_dim": model["head_dim"]}]


# -- published against as run ---------------------------------------------------

WIDTHS = ("hidden_size", "full_attention_interval", "linear_num_key_heads",
          "linear_num_value_heads", "linear_key_head_dim",
          "linear_value_head_dim", "linear_conv_kernel_dim",
          "num_attention_heads", "num_key_value_heads", "head_dim",
          "partial_rotary_factor", "rope_theta", "num_experts_per_tok",
          "moe_intermediate_size", "shared_expert_intermediate_size",
          "rms_norm_eps")


def width_pairs(config: dict) -> list:
    """``(name, as run, as published)`` for every size the source states:
    a test holds each pair equal unless ``reduced`` names it.  The model
    dict, the file's top level (the catalog's keys, as run) and
    ``published`` are all three held together."""
    m, p = config[MODEL_KEY], config["published"]
    pairs = [(k, m[k], p[k]) for k in WIDTHS]
    pairs += [("router_width", m["router_width"], p["num_experts"]),
              ("num_experts", m["num_experts"], p["num_experts"]),
              ("vocab_size", m["vocab_size"], p["vocab_size"]),
              ("num_hidden_layers", m["num_hidden_layers"],
               p["num_hidden_layers"]),
              ("chunk_size", m["chunk_size"],
               config["assumed_sizes"]["chunk_size"]),
              ("initializer_range", m["initializer_range"],
               config["assumed_sizes"]["initializer_range"])]
    # the file's top level repeats the source's keys as run
    pairs += [("top_level." + k, config[k], m[k]) for k in WIDTHS
              + ("num_experts", "vocab_size", "num_hidden_layers")]
    return pairs
