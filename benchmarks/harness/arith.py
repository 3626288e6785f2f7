"""Metric arithmetic owned by the benchmark: the operations and bytes a
call needs, computed from shapes. Later PRs cannot change these, so every
PR's numbers are computed the same way.

A configuration file names its arithmetic module (`"arith"`, a module
path under `benchmarks/`; this one for the dense decoders). `run.collect`
hands the readers that module and what its `widths(config)` makes of the
file's own keys, so a configuration with experts or another attention
brings a module of its own (`widths`, `train_flops_per_token`,
`flash_attention_flops`, `decode_read_bytes`) and edits nothing here.

`train_flops_per_token` follows `ray_tpu.train.spmd.train_flops_per_token`
(copied; the original is listed in PERF.md for deletion) with one
difference: attention is counted causal, i.e. half of the full square,
because that is what the forward and backward passes require.
"""

from __future__ import annotations


def widths(config: dict) -> dict:
    """A dense decoder's sizes from the published keys. Key-value heads,
    a head size that is not hidden / heads and an untied output matrix
    are data here (the unembedding costs the same tied or not)."""
    return {"vocab_size": config["vocab_size"],
            "d_model": config["hidden_size"],
            "n_layers": config["num_hidden_layers"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "d_ff": config["intermediate_size"],
            "max_seq_len": config["max_position_embeddings"],
            "tied": bool(config["tie_word_embeddings"])}


def matmul_params(widths: dict) -> int:
    """Parameters inside the per-layer matrix multiplications."""
    d, f, layers = widths["d_model"], widths["d_ff"], widths["n_layers"]
    h = widths["n_heads"] * widths["head_dim"]
    kv = widths.get("n_kv_heads", widths["n_heads"]) * widths["head_dim"]
    return layers * (d * h + 2 * d * kv + h * d + 3 * d * f)


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


def decode_read_bytes(context_tokens: float, kv_bytes_per_token: int) -> float:
    """Bytes of cached keys and values one decode step has to read: the
    context of every decoding stream, at the pool's bytes a position
    (all layers, K and V: `stats()["kv_bytes_per_token"]`). Padding to
    whole blocks and idle slots are not required work."""
    return context_tokens * kv_bytes_per_token
