"""Metric arithmetic for training a decoder with latent attention and
routed experts, one chip's share (`benchmarks/configs/kanana-2-30b-a3b.json`
names this module as its `arith`): the operations a step needs, computed
from the file's own keys. Later PRs cannot change these.

Counted as `arith.py` counts a dense decoder: forward and backward, no
recomputation, attention causal (half the square). What is this family's
own: queries and keys are `qk_nope_head_dim + qk_rope_head_dim` wide and
values `v_head_dim`; a token's routed experts are counted by the share of
them held here, `n_routed_experts / published width x num_experts_per_tok`
of an expert a token, which is exact over the chips that share a layer
taken together (each pair is computed on exactly one of them).
"""

from __future__ import annotations


def widths(config: dict) -> dict:
    lo = config.get("layers_from", 0)
    hi = lo + config["num_hidden_layers"]
    dense = sum(lo <= i < hi for i in range(config["first_k_dense_replace"]))
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "qk_dim": qk,
        "v_dim": config["v_head_dim"],
        # what `flash_roofline.py` hands `flash_attention_flops`: the mean
        # of the two widths, which makes the six-matmul count exact. The
        # forward's two products contract 192 and 128, 2 x 160 together;
        # of the backward's four required ones dQ and dK contract 192,
        # dP and dV 128, 4 x 160 together.
        "head_dim": (qk + config["v_head_dim"]) / 2,
        "kv_rank": config["kv_lora_rank"],
        "rope_dim": config["qk_rope_head_dim"],
        "nope_dim": config["qk_nope_head_dim"],
        "d_ff": config["intermediate_size"],
        "expert_ff": config["moe_intermediate_size"],
        "shared_experts": config["n_shared_experts"],
        "experts_held": config["n_routed_experts"],
        "router_width": config.get("published", {}).get(
            "n_routed_experts", config["n_routed_experts"]),
        "experts_per_token": config["num_experts_per_tok"],
        "dense_layers": dense,
        "sparse_layers": config["num_hidden_layers"] - dense,
    }


def attention_params(w: dict) -> int:
    """A layer's attention matrices: W_q, W_kva, W_kvb, W_o."""
    d, h = w["d_model"], w["n_heads"]
    return (d * h * w["qk_dim"] + d * (w["kv_rank"] + w["rope_dim"])
            + w["kv_rank"] * h * (w["nope_dim"] + w["v_dim"])
            + h * w["v_dim"] * d)


def routed_experts_per_token(w: dict) -> float:
    """Experts of a token's `experts_per_token` that are held here, on
    average over the chips that share the layer."""
    return w["experts_held"] / w["router_width"] * w["experts_per_token"]


def active_matmul_params(w: dict) -> float:
    """Parameters a token passes through in matrix products, all layers
    and the head (the embedding is a lookup)."""
    d = w["d_model"]
    expert = 3 * d * w["expert_ff"]
    sparse = (d * w["router_width"] + w["shared_experts"] * expert
              + routed_experts_per_token(w) * expert)
    return (w["n_layers"] * attention_params(w)
            + w["dense_layers"] * 3 * d * w["d_ff"]
            + w["sparse_layers"] * sparse + d * w["vocab_size"])


def held_params(w: dict) -> int:
    """Parameters resident on the chip: every held expert whole."""
    d = w["d_model"]
    expert = 3 * d * w["expert_ff"]
    sparse = (d * w["router_width"] + w["router_width"]
              + (w["shared_experts"] + w["experts_held"]) * expert)
    norms = w["n_layers"] * (2 * d + w["kv_rank"]) + d
    return (w["n_layers"] * attention_params(w)
            + w["dense_layers"] * 3 * d * w["d_ff"]
            + w["sparse_layers"] * sparse + 2 * d * w["vocab_size"] + norms)


def train_flops_per_token(w: dict, seq_len: int) -> float:
    """Operations the forward and backward passes need per trained token
    (backward = 2 x forward; recomputation not counted): two a parameter
    a product, and causal attention's scores and weighted values over
    half the sequence's keys at the two widths."""
    attn = (2 * (w["qk_dim"] + w["v_dim"]) * w["n_heads"] * seq_len / 2
            * w["n_layers"])
    return 3.0 * (2 * active_matmul_params(w) + attn)


def flash_attention_flops(batch: int, seq_len: int, n_heads: int,
                          head_dim: float, layers: int) -> float:
    """`arith.flash_attention_flops`, word for word: causal forward + dQ +
    dK/dV for one optimizer step, required = 2 forward + 4 backward
    products over the lower triangle, each contracting `head_dim`
    (`widths`' mean of the two)."""
    tri = seq_len * seq_len / 2
    per_matmul = 2 * tri * head_dim
    return batch * n_heads * layers * (2 + 4) * per_matmul


def expert_train_flops(w: dict, pairs: float) -> float:
    """Operations the held experts' three matrices need for `pairs` live
    (token, expert) pairs, forward and backward: 3 matrices x (the
    forward's product, the backward's two: towards the input and towards
    the matrix) x 2 x d_model x expert_ff a pair. Making gate and up
    again in the backward is not required work."""
    return 18.0 * w["d_model"] * w["expert_ff"] * pairs
