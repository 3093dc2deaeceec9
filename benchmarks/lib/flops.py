"""Operations and bytes computed from shapes: the benchmark's own arithmetic.

Nothing here reads XLA's ``cost_analysis()`` (it counts recomputation and
fusion artefacts).  A configuration is the dict of its ``GPTConfig`` fields.
"""
from __future__ import annotations


def model_flops_per_token(cfg: dict, seq: int) -> float:
    """Training FLOPs one token needs, forward and backward, no recompute.

    6 x (matmul parameters) + 6 x L x s x h: every weight matrix is used by
    one multiply-add forward and two backward (6 FLOPs a parameter); causal
    attention multiplies each query with half the sequence in two matmuls
    forward (2 x s x h FLOPs a layer) and twice that backward.  For
    gpt2-medium at s = 1,024: 2.272 GFLOP.
    """
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    n_layers, vocab = cfg["num_hidden_layers"], cfg["vocab_size"]
    matmul_params = n_layers * (4 * h * h + 2 * h * f) + vocab * h
    return 6.0 * matmul_params + 6.0 * n_layers * seq * h


def flash_flops(batch: int, seq: int, heads: int, head_dim: int) -> dict:
    """FLOPs of one layer's causal flash attention: the forward pass has 2
    matmuls (QK^T, PV), the backward 5 (QK^T again, dV, dP, dQ, dK); each is
    2 x b x H x s x s x d FLOPs, halved by the causal mask."""
    one = 2.0 * batch * heads * seq * seq * head_dim / 2.0
    return {"fwd": 2 * one, "bwd": 5 * one}


def flash_bytes(batch: int, seq: int, heads: int, head_dim: int,
                itemsize: int = 2) -> dict:
    """Least HBM traffic of one layer's flash attention: the forward reads
    q, k, v and writes o and the f32 log-sum-exp; the backward reads q, k,
    v, o, do and the log-sum-exp and writes dq, dk, dv."""
    tensor = batch * seq * heads * head_dim * itemsize
    lse = batch * heads * seq * 4
    return {"fwd": 4 * tensor + lse, "bwd": 8 * tensor + lse}


def flash_least_seconds(cfg: dict, batch: int, seq: int, peak: dict) -> dict:
    """The least time a chip with ``peak`` could take for the flash calls of
    one training step (every layer, forward and backward), and which of the
    two bounds sets it."""
    heads = cfg["num_attention_heads"]
    head_dim = cfg["hidden_size"] // heads
    ops = flash_flops(batch, seq, heads, head_dim)
    moved = flash_bytes(batch, seq, heads, head_dim)
    n_layers = cfg["num_hidden_layers"]
    by_compute = n_layers * (ops["fwd"] + ops["bwd"]) / peak["flops"]
    by_bytes = (n_layers * (moved["fwd"] + moved["bwd"])
                / peak["hbm_bytes_per_s"])
    return {"seconds": max(by_compute, by_bytes),
            "bound": "compute" if by_compute >= by_bytes else "bandwidth",
            "compute_seconds": by_compute, "bandwidth_seconds": by_bytes}
