"""Operations and bytes of the ``qwen3_next`` family's two kinds of kernel
work, computed from shapes alone: the same work whatever implements it
(chunked contractions or a Pallas kernel; ``ragged_dot``, megablox or the
repo's own).  ``lib/flops.py`` keeps the flash kernel's.  ``model`` is the
model dict as run (the configuration file's ``qwen3_next_config``).
"""
from __future__ import annotations


def delta_rule_flops(batch: int, seq: int, model: dict) -> dict:
    """FLOPs of one Gated DeltaNet layer's chunked delta rule.  A chunk of
    C tokens, Hk key heads and Hv value heads of 128 lanes (d_k, d_v),
    forward:

    * five (C, C) products over a head's lanes: ``K K^T`` and ``Q K^T`` (2
      C^2 d_k each, ONCE A KEY HEAD: the value heads of a key head share
      them and differ by their decays alone), the inverse times ``beta V``
      and times the decayed ``beta K``, the masked ``Q K^T`` times the
      corrections (2 C^2 d each, a value head);
    * three state products a value head, 2 C d_k d_v each: what the
      entering state takes off the corrections, what it adds to the
      output, the chunk's own state;
    * the forward substitution of the (C, C) unit lower-triangular system,
      a value head: row i costs 2 i^2, 2 C^3 / 3 in all.

    The backward needs each product twice (one a factor): 2 x forward.
    Decays, gates, the normalisations and the carried state's decay are
    elementwise and not counted."""
    c = model["chunk_size"]
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    chunks = batch * seq / c
    fwd = chunks * (
        2 * hk * 2 * c * c * dk                  # K K^T, Q K^T
        + hv * 2 * c * c * (dv + dk + dv)        # T (beta V), T (beta K), A U
        + hv * 3 * 2 * c * dk * dv               # the state products
        + hv * 2 * c ** 3 / 3)                   # the substitution
    return {"fwd": fwd, "bwd": 2 * fwd, "total": 3 * fwd}


def delta_rule_bytes(batch: int, seq: int, model: dict,
                     itemsize: int = 2) -> dict:
    """Least HBM traffic of one layer's delta rule: q, k, v and the float32
    g and beta read and o written once forward; backward reads them and do
    and writes the five gradients."""
    hk, hv = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    tokens = batch * seq
    qk = 2 * tokens * hk * dk * itemsize
    v = tokens * hv * dv * itemsize
    gates = 2 * tokens * hv * 4
    return {"fwd": qk + v + gates + v, "bwd": 2 * (qk + v + gates) + v}


def delta_rule_least_seconds(layers: int, batch: int, seq: int, model: dict,
                             peak: dict) -> float:
    """The least time a chip with ``peak`` could take for the delta rules of
    one training step, forward and backward, each bound by the slower of
    compute and bandwidth."""
    ops = delta_rule_flops(batch, seq, model)
    moved = delta_rule_bytes(batch, seq, model)
    return layers * sum(max(ops[k] / peak["flops"],
                            moved[k] / peak["hbm_bytes_per_s"])
                        for k in ("fwd", "bwd"))


def expected_held_rows(batch: int, seq: int, model: dict) -> float:
    """Rows that uniform routing sends to the experts held here, a layer:
    tokens x experts a token x held / router width."""
    return (batch * seq * model["num_experts_per_tok"]
            * model["num_experts"] / model["router_width"])


def gated_grouped_flops(rows: float, model: dict) -> dict:
    """FLOPs of one gated expert layer's grouped products over ``rows``
    rows: gate and up (hidden -> width) and down (width -> hidden), each
    once forward and twice backward (its input's gradient, its weight's):
    nine products of 2 x rows x hidden x width."""
    one = 2.0 * rows * model["hidden_size"] * model["moe_intermediate_size"]
    return {"fwd": 3 * one, "bwd": 6 * one, "total": 9 * one}


def gated_grouped_bytes(rows: float, model: dict, itemsize: int = 2) -> float:
    """Least HBM traffic of ONE of the nine grouped products: its rows in
    and out (or, for a weight's gradient, both sets of rows in) and the
    held experts' weights once."""
    h, f = model["hidden_size"], model["moe_intermediate_size"]
    return (rows * (h + f) + model["num_experts"] * h * f) * itemsize


def gated_grouped_least_seconds(layers: int, batch: int, seq: int,
                                model: dict, peak: dict) -> float:
    """The least time for the grouped products of one training step over
    the expected held rows: nine products a layer, each bound by the slower
    of compute and bandwidth."""
    rows = expected_held_rows(batch, seq, model)
    one = gated_grouped_flops(rows, model)["total"] / 9
    return layers * 9 * max(one / peak["flops"],
                            gated_grouped_bytes(rows, model)
                            / peak["hbm_bytes_per_s"])
