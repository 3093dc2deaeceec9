"""Operations and bytes of the ``nemotron_h`` family's two new kernels,
computed from shapes alone: the same work whatever implements it (chunked
contractions or a Pallas kernel; ``ragged_dot``, megablox or the repo's
own).  ``lib/flops.py`` keeps the flash kernel's.  ``model`` is the model
dict as run (the configuration file's ``nemotron_h_config``).
"""
from __future__ import annotations


def scan_flops(batch: int, seq: int, model: dict) -> dict:
    """FLOPs of one Mamba-2 layer's chunked scan.  A chunk of L tokens, H
    heads of P, G groups of state N, forward:

    * ``C.B^T`` inside the chunk, causal: G x L^2 x N (2 L^2 N, halved);
    * the decayed scores times x, causal: H x L^2 x P;
    * the chunk's own state, ``x (x) B`` summed over the chunk: 2 H L P N;
    * the entering state read out through C: 2 H L N P.

    The backward needs each product twice (one a factor): 2 x forward.
    The recurrence between chunk states, the decays and the D skip are
    elementwise and not counted."""
    chunk = model["chunk_size"]
    heads, p = model["mamba_num_heads"], model["mamba_head_dim"]
    groups, n = model["n_groups"], model["ssm_state_size"]
    chunks = batch * seq / chunk
    fwd = chunks * (groups * chunk * chunk * n + heads * chunk * chunk * p
                    + 4 * heads * chunk * p * n)
    return {"fwd": fwd, "bwd": 2 * fwd, "total": 3 * fwd}


def scan_bytes(batch: int, seq: int, model: dict, itemsize: int = 2) -> dict:
    """Least HBM traffic of one layer's scan: x, B, C and the float32 dt
    read and y written once forward; backward reads them and dy and writes
    the four gradients."""
    heads, p = model["mamba_num_heads"], model["mamba_head_dim"]
    groups, n = model["n_groups"], model["ssm_state_size"]
    tokens = batch * seq
    x = tokens * heads * p * itemsize
    bc = 2 * tokens * groups * n * itemsize
    dt = tokens * heads * 4
    return {"fwd": x + bc + dt + x, "bwd": 2 * (x + bc + dt) + x}


def scan_least_seconds(layers: int, batch: int, seq: int, model: dict,
                       peak: dict) -> float:
    """The least time a chip with ``peak`` could take for the scans of one
    training step, forward and backward, each bound by the slower of
    compute and bandwidth."""
    ops, moved = scan_flops(batch, seq, model), scan_bytes(batch, seq, model)
    return layers * sum(max(ops[k] / peak["flops"],
                            moved[k] / peak["hbm_bytes_per_s"])
                        for k in ("fwd", "bwd"))


def expected_held_rows(batch: int, seq: int, model: dict) -> float:
    """Rows that uniform routing sends to the experts held here, a layer:
    tokens x experts a token x held / router width."""
    return (batch * seq * model["num_experts_per_tok"]
            * model["n_routed_experts"] / model["router_width"])


def grouped_flops(rows: float, model: dict) -> dict:
    """FLOPs of one expert layer's grouped products over ``rows`` rows: up
    (hidden -> width) and down (width -> hidden), each once forward and
    twice backward (its input's gradient, its weight's)."""
    one = 2.0 * rows * model["hidden_size"] * model["moe_intermediate_size"]
    return {"fwd": 2 * one, "bwd": 4 * one, "total": 6 * one}


def grouped_bytes(rows: float, model: dict, itemsize: int = 2) -> float:
    """Least HBM traffic of ONE of the six grouped products: its rows in
    and out (or, for a weight's gradient, both sets of rows in) and the
    held experts' weights once."""
    h, f = model["hidden_size"], model["moe_intermediate_size"]
    return (rows * (h + f) + model["n_routed_experts"] * h * f) * itemsize


def grouped_least_seconds(layers: int, batch: int, seq: int, model: dict,
                          peak: dict) -> float:
    """The least time for the grouped products of one training step over
    the expected held rows: six products a layer, each bound by the slower
    of compute and bandwidth."""
    rows = expected_held_rows(batch, seq, model)
    one = grouped_flops(rows, model)["total"] / 6
    return layers * 6 * max(one / peak["flops"],
                            grouped_bytes(rows, model)
                            / peak["hbm_bytes_per_s"])
