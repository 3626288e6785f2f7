"""Metric arithmetic for training a decoder of window and full attention
layers over routed experts, one chip's share
(`benchmarks/configs/mellum2-12b-a2.5b.json` names this module as its
`arith`): the operations a step needs, computed from the file's own keys.
Later PRs cannot change these.

Counted as `latent_moe_arith.py` counts: forward and backward, no
recomputation; a token's routed experts by the share of them held here,
`num_experts / published width x num_experts_per_tok` of an expert a
token, which is exact over the chips that share a layer taken together.
What is this family's own: attention is counted by the (query, key) pairs
a layer's mask lets through, exactly. A full layer's position i reads
i + 1 keys, T (T + 1) / 2 pairs a sequence; a window layer's reads
min(i + 1, window), `band_pairs`. Every pair costs each of the 32 query
heads two products forward and four backward, each 2 x head_dim
operations; the 4 key-value heads change the bytes, not the operations.
"""

from __future__ import annotations


def widths(config: dict) -> dict:
    lo = config.get("layers_from", 0)
    n = config["num_hidden_layers"]
    kinds = config["layer_types"][lo:lo + n]
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": n,
        "window_layers": kinds.count("sliding_attention"),
        "full_layers": kinds.count("full_attention"),
        "window": config["sliding_window"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "expert_ff": config["moe_intermediate_size"],
        "experts_held": config["num_experts"],
        "router_width": config.get("published", {}).get(
            "num_experts", config["num_experts"]),
        "experts_per_token": config["num_experts_per_tok"],
    }


def attention_params(w: dict) -> int:
    """A layer's attention matrices: W_q, W_k, W_v, W_o."""
    d, hd = w["d_model"], w["head_dim"]
    return 2 * d * w["n_heads"] * hd + 2 * d * w["n_kv_heads"] * hd


def expert_params(w: dict) -> int:
    return 3 * w["d_model"] * w["expert_ff"]


def routed_experts_per_token(w: dict) -> float:
    """Experts of a token's `experts_per_token` that are held here, on
    average over the chips that share the layer."""
    return w["experts_held"] / w["router_width"] * w["experts_per_token"]


def active_matmul_params(w: dict) -> float:
    """Parameters a token passes through in matrix products, all layers
    and the head (the embedding is a lookup)."""
    d = w["d_model"]
    layer = (attention_params(w) + d * w["router_width"]
             + routed_experts_per_token(w) * expert_params(w))
    return w["n_layers"] * layer + d * w["vocab_size"]


def held_params(w: dict) -> int:
    """Parameters resident on the chip: every held expert whole, the
    embedding and the untied head, the norms' scales."""
    d = w["d_model"]
    layer = (attention_params(w) + d * w["router_width"]
             + w["experts_held"] * expert_params(w) + 2 * d)
    return w["n_layers"] * layer + 2 * d * w["vocab_size"] + d


def full_pairs(seq_len: int) -> int:
    """(query, key) pairs of one causal sequence."""
    return seq_len * (seq_len + 1) // 2


def band_pairs(seq_len: int, window: int) -> int:
    """The same under a window: the sum over i of min(i + 1, window)."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def _attention_flops(w: dict, pairs: float, products: int) -> float:
    return products * 2.0 * w["head_dim"] * w["n_heads"] * pairs


def train_flops_per_token(w: dict, seq_len: int) -> float:
    """Operations the forward and backward passes need per trained token
    (backward = 2 x forward; recomputation not counted): two a parameter
    a product, and attention's scores and weighted values over the pairs
    each kind of layer reads."""
    pairs = (w["full_layers"] * full_pairs(seq_len)
             + w["window_layers"] * band_pairs(seq_len, w["window"]))
    return 3.0 * (2 * active_matmul_params(w)
                  + _attention_flops(w, pairs / seq_len, 2))


def band_attention_flops(w: dict, seq_len: float) -> float:
    """What the window layers' attention needs for one sequence of a
    step, all of them: forward + dQ + dK/dV, six products a pair of the
    band."""
    return w["window_layers"] * _attention_flops(
        w, band_pairs(int(seq_len), w["window"]), 6)


def full_attention_flops(w: dict, seq_len: float) -> float:
    """The same for the full layers: six products a pair of the
    triangle."""
    return w["full_layers"] * _attention_flops(
        w, full_pairs(int(seq_len)), 6)


def expert_train_flops(w: dict, pairs: float) -> float:
    """`latent_moe_arith.expert_train_flops`, word for word: 3 matrices x
    (the forward's product, the backward's two) x 2 x d_model x expert_ff
    a live (token, expert) pair."""
    return 18.0 * w["d_model"] * w["expert_ff"] * pairs
