"""Metric arithmetic owned by the benchmark: the operations a call needs,
computed from shapes. Later PRs cannot change these, so every PR's numbers
are computed the same way.

`train_flops_per_token` follows `ray_tpu.train.spmd.train_flops_per_token`
(copied; the original is listed in PERF.md for deletion) with one
difference: attention is counted causal, i.e. half of the full square,
because that is what the forward and backward passes require.
"""

from __future__ import annotations


def matmul_params(widths: dict) -> int:
    """Parameters inside the per-layer matrix multiplications."""
    d, f, layers = widths["d_model"], widths["d_ff"], widths["n_layers"]
    h = widths["n_heads"] * widths["head_dim"]
    return layers * (3 * d * h + h * d + 3 * d * f)


def train_flops_per_token(widths: dict, seq_len: int) -> float:
    """Operations the forward and backward passes need per trained
    token (backward = 2 x forward; recomputation not counted)."""
    d, layers = widths["d_model"], widths["n_layers"]
    h = widths["n_heads"] * widths["head_dim"]
    matmuls = 2 * matmul_params(widths) / layers      # qkv + o + gated mlp
    attn = 2 * 2 * seq_len * h / 2                    # scores + p@v, causal
    unembed = 2 * d * widths["vocab_size"]
    return 3.0 * (layers * (matmuls + attn) + unembed)


def flash_attention_flops(batch: int, seq_len: int, n_heads: int,
                          head_dim: int, layers: int) -> float:
    """Causal flash attention, forward + dQ + dK/dV, for one optimizer
    step: forward is two matmuls over the lower triangle (QK^T, PV), the
    two backward kernels recompute the scores and take five between them
    (S twice, dP twice, dQ, dK, dV = 7 in all, minus the 2 recomputes
    that are not required work): required = 2 forward + 4 backward."""
    tri = seq_len * seq_len / 2
    per_matmul = 2 * tri * head_dim
    return batch * n_heads * layers * (2 + 4) * per_matmul
