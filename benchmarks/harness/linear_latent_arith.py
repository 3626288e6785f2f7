"""Metric arithmetic for a decoder that mixes delta-rule linear attention
(KDA) layers with latent-attention layers and routed experts
(`benchmarks/configs/ling-3.0-flash-vl.json` names this module as its
`arith`): parameters, the bytes of a sequence's state, the bytes a decode
step needs and the operations a prefill chunk needs, computed from the
file's own keys. Later PRs cannot change these.

Every count is of the mechanism, never of a kernel: a chunk's operations
are those of its live tokens, whatever bucket they were padded to and
however a kernel splits its operands or solves inside a sub-chunk; a
step's bytes are the decoding sequences' states and rows, not idle slots'
or a page's padding.

- `kda_step` is bound by bytes: a decode step has to read every decoding
  sequence's whole state once, in every KDA layer (H x d x d float32). A
  read-modify-write reads it and writes it, so it can reach 50 % of this;
  no formulation reads less than the state once.
- `kda_chunk` is bound by operations: a token reads a head's state twice
  (the delta rule's prediction k^T S and the output q^T S) and updates it
  once (the rank-one correction), d x d multiply-adds each: 6 d^2
  operations a token a head a layer. The chunk form turns these into
  matmuls a sub-chunk plus a triangular solve inside it; the solve is the
  formulation's and is not counted.
- `latent_decode` is bound by bytes: one row of `kv_lora_rank +
  qk_rope_head_dim` values a cached position in every latent layer.
- The expert kernels' functions keep the names `latent_arith` gives them.
"""

from __future__ import annotations

# one cached position of one latent layer, bfloat16: (512 + 64) x 2 B.
# `decode_read_bytes` is handed no widths (`layer_metrics/
# decode_roofline.py`); benchmarks/tests hold this to the file's keys
LATENT_ROW_BYTES = 1152


def widths(config: dict) -> dict:
    lo = config.get("layers_from", 0)
    n = config["num_hidden_layers"]
    dense = lo + config["first_k_dense_replace"]
    latent = sum((i + 1) % config["layer_group_size"] == 0
                 for i in range(lo, lo + n))
    value_bytes = 2 if config["program"]["model"]["dtype"] == "bfloat16" \
        else 4
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": n,
        "kda_layers": n - latent,
        "latent_layers": latent,
        "dense_layers": sum(i < dense for i in range(lo, lo + n)),
        "sparse_layers": sum(i >= dense for i in range(lo, lo + n)),
        "n_heads": config["num_attention_heads"],
        "head_dim": config["head_dim"],
        "conv_taps": config["short_conv_kernel_size"],
        "kv_rank": config["kv_lora_rank"],
        "nope_dim": config["qk_nope_head_dim"],
        "rope_dim": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "latent_row_values": config["kv_lora_rank"]
        + config["qk_rope_head_dim"],
        "d_ff": config["intermediate_size"],
        "expert_ff": config["moe_intermediate_size"],
        "shared_ff": config["moe_shared_expert_intermediate_size"],
        "experts_held": config["num_experts"],
        "router_width": config.get("published", {}).get(
            "num_experts", config["num_experts"]),
        "experts_per_token": config["num_experts_per_tok"],
        "state_bytes_per_value": 4,
        "value_bytes": value_bytes,
    }


def kda_layer_parameters(w: dict) -> int:
    d, hd = w["d_model"], w["n_heads"] * w["head_dim"]
    return (4 * d * hd + d * hd             # q, k, v, o; the gate W_f
            + 3 * w["conv_taps"] * hd       # the three convolutions
            + hd + w["n_heads"]             # b_f, A
            + 2 * d * w["n_heads"]          # beta, the output gate
            + w["head_dim"])                # the head norm's scale


def latent_layer_parameters(w: dict) -> int:
    d, nh = w["d_model"], w["n_heads"]
    return (d * nh * (w["nope_dim"] + w["rope_dim"])
            + d * w["latent_row_values"] + w["kv_rank"]
            + w["kv_rank"] * nh * (w["nope_dim"] + w["v_dim"])
            + nh * w["v_dim"] * d + d * nh)


def expert_parameters(w: dict) -> int:
    return 3 * w["expert_ff"] * w["d_model"]


def ffn_parameters(w: dict, sparse: bool) -> int:
    d = w["d_model"]
    if not sparse:
        return 3 * d * w["d_ff"]
    return (w["experts_held"] * expert_parameters(w)
            + 3 * d * w["shared_ff"] + d * w["router_width"]
            + w["router_width"])


def parameters(w: dict) -> int:
    """As run: the layers (two norm scales each), embedding and head
    both, the final norm."""
    d = w["d_model"]
    return (w["kda_layers"] * kda_layer_parameters(w)
            + w["latent_layers"] * latent_layer_parameters(w)
            + w["dense_layers"] * ffn_parameters(w, False)
            + w["sparse_layers"] * ffn_parameters(w, True)
            + w["n_layers"] * 2 * d + 2 * w["vocab_size"] * d + d)


def state_bytes(w: dict) -> int:
    """One sequence's KDA states, all KDA layers: S [d, d] a head,
    float32."""
    return (w["kda_layers"] * w["n_heads"] * w["head_dim"] ** 2
            * w["state_bytes_per_value"])


def tail_bytes(w: dict) -> int:
    """One sequence's convolution tails: the last taps - 1 positions of
    the three projections, all KDA layers, as the activations' type."""
    return (w["kda_layers"] * (w["conv_taps"] - 1) * 3 * w["n_heads"]
            * w["head_dim"] * w["value_bytes"])


def state_read_bytes(w: dict, streams: float) -> float:
    """Bytes of state one decode step has to read: every decoding
    sequence's, once."""
    return streams * state_bytes(w)


def chunk_required_ops(w: dict, tokens: float) -> float:
    """Operations the KDA of a prefill chunk of `tokens` live tokens
    needs, all KDA layers: two reads of the state and one update."""
    return (tokens * w["kda_layers"] * w["n_heads"]
            * 6 * w["head_dim"] ** 2)


def decode_read_bytes(context_tokens: float, kv_bytes_per_token=None) -> float:
    """Bytes of latent rows one decode step has to read: the context of
    every decoding stream, one row a position for the one latent layer.
    (The engine's `kv_bytes_per_token` also spreads a sequence's state
    over `max_len`; a step does not read that a position, so it is not
    taken.)"""
    return context_tokens * LATENT_ROW_BYTES


def held_expert_bytes(w: dict) -> float:
    """Bytes of the held routed experts' weights, all sparse layers: what
    a prefill chunk has to read when its tokens reach every held expert
    (512 tokens, 8 of 512 each, 128 held: a held expert gets 8 tokens on
    average and is missed with probability about e^-8)."""
    return (w["experts_held"] * expert_parameters(w) * w["value_bytes"]
            * w["sparse_layers"])


def step_required_bytes(w: dict, streams: float,
                        context_tokens: float) -> dict:
    """What a decode step of `streams` decoding sequences over
    `context_tokens` cached positions has to move, by part."""
    touched = w["experts_held"] * (1 - (1 - 1 / w["router_width"]) ** (
        streams * w["experts_per_token"]))
    other = (w["kda_layers"] * kda_layer_parameters(w)
             + w["latent_layers"] * latent_layer_parameters(w)
             + w["dense_layers"] * ffn_parameters(w, False)
             + w["sparse_layers"] * (3 * w["d_model"] * w["shared_ff"]
                                     + w["d_model"] * w["router_width"])
             + w["vocab_size"] * w["d_model"]) * w["value_bytes"]
    return {
        "experts_touched": touched * expert_parameters(w) * w["value_bytes"]
        * w["sparse_layers"],
        "states_read_and_written": 2 * state_read_bytes(w, streams),
        "other_weights": other,
        "latent_rows": decode_read_bytes(context_tokens)
        * w["latent_layers"],
    }
