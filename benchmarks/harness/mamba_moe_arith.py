"""Metric arithmetic for a decoder whose layers are Mamba-2 state-space
layers, LatentMoE expert layers and grouped-head attention layers, each a
mixer or a feed-forward part alone
(`benchmarks/configs/nemotron-3-super.json` names this module as its
`arith`): parameters, the bytes of a sequence's state, the bytes a decode
step needs and the operations a prefill chunk needs, computed from the
file's own keys. Later PRs cannot change these.

Every count is of the mechanism, never of a kernel: a chunk's operations
are those of its live tokens, whatever bucket they were padded to and
however a kernel splits its operands or makes a group's scores again; a
step's bytes are the decoding sequences' states and rows, not idle slots'
or a page's padding.

- `mamba2_step` is bound by bytes: a decode step has to read every
  decoding sequence's whole state once, in every state layer (H x P x N
  float32). A read-modify-write reads it and writes it, so it can reach
  50 % of this; no formulation reads less than the state once.
- `mamba2_chunk` is bound by operations, those of the chunked form (the
  state-space duality) at sub-blocks of `chunk_size`: a live token's
  scores `C B^T` against its sub-block (N multiply-adds a pair of
  positions, made once a group), their product with `x` (P a pair a
  head), the read of the carried state (`C S`: P N a head) and its
  update (`B^T x`: P N a head). The sub-block's triangle is counted
  whole, as the matmul makes it.
- the attention layer's decode is bound by bytes: one key row and one
  value row of `num_key_value_heads` heads a cached position.
- The expert kernels' functions keep the names `latent_arith` gives them;
  an expert here has two matrices, not three.
"""

from __future__ import annotations

# one cached position of the attention layer, bfloat16: keys and values
# of 2 heads of 128. `decode_read_bytes` is handed no widths
# (`layer_metrics/decode_roofline.py`); benchmarks/tests hold this to the
# file's keys
ROW_BYTES = 1024


def widths(config: dict) -> dict:
    lo = config.get("layers_from", 0)
    n = config["num_hidden_layers"]
    kinds = config["hybrid_override_pattern"][lo:lo + n]
    value_bytes = 2 if config["program"]["model"]["dtype"] == "bfloat16" \
        else 4
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": n,
        "mamba_layers": kinds.count("M"),
        "attention_layers": kinds.count("*"),
        "expert_layers": kinds.count("E"),
        "mamba_heads": heads,
        "mamba_head_dim": p,
        "n_groups": groups,
        "state_size": state,
        "inner": heads * p,
        "conv_channels": heads * p + 2 * groups * state,
        "conv_taps": config["conv_kernel"],
        "sub_block": config["chunk_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "latent": config["moe_latent_size"],
        "expert_ff": config["moe_intermediate_size"],
        "shared_ff": config["moe_shared_expert_intermediate_size"],
        "experts_held": config["n_routed_experts"],
        "router_width": config.get("published", {}).get(
            "n_routed_experts", config["n_routed_experts"]),
        "experts_per_token": config["num_experts_per_tok"],
        "state_bytes_per_value": 4,
        "value_bytes": value_bytes,
        "row_bytes": 2 * config["num_key_value_heads"] * config["head_dim"]
        * value_bytes,
    }


def mamba_layer_parameters(w: dict) -> int:
    d, inner, ch = w["d_model"], w["inner"], w["conv_channels"]
    return (d * (inner + ch + w["mamba_heads"])     # W_in: z, xBC, dt
            + (w["conv_taps"] + 1) * ch             # the taps and their bias
            + 3 * w["mamba_heads"]                  # dt_bias, A_log, D
            + inner                                 # the grouped norm's scale
            + inner * d)                            # W_out


def attention_layer_parameters(w: dict) -> int:
    d, hd = w["d_model"], w["head_dim"]
    return 2 * d * w["n_heads"] * hd + 2 * d * w["n_kv_heads"] * hd


def expert_parameters(w: dict) -> int:
    return 2 * w["expert_ff"] * w["latent"]


def expert_layer_parameters(w: dict, experts: int | None = None) -> int:
    """An expert layer with `experts` routed experts (the held ones where
    none is given): router and its bias, both latent projections, the
    shared expert."""
    d = w["d_model"]
    held = w["experts_held"] if experts is None else experts
    return (held * expert_parameters(w)
            + d * w["router_width"] + w["router_width"]
            + 2 * d * w["latent"] + 2 * d * w["shared_ff"])


def parameters(w: dict) -> int:
    """As run: the layers (one norm scale each), embedding and head both,
    the final norm."""
    d = w["d_model"]
    return (w["mamba_layers"] * mamba_layer_parameters(w)
            + w["attention_layers"] * attention_layer_parameters(w)
            + w["expert_layers"] * expert_layer_parameters(w)
            + w["n_layers"] * d + 2 * w["vocab_size"] * d + d)


def state_bytes(w: dict) -> int:
    """One sequence's recurrent states, all state layers: S [P, N] a
    head, float32."""
    return (w["mamba_layers"] * w["mamba_heads"] * w["mamba_head_dim"]
            * w["state_size"] * w["state_bytes_per_value"])


def tail_bytes(w: dict) -> int:
    """One sequence's convolution tails as stored: the last taps - 1
    positions of xBC, all state layers, float32 bytes."""
    return (w["mamba_layers"] * (w["conv_taps"] - 1) * w["conv_channels"]
            * w["state_bytes_per_value"])


def state_read_bytes(w: dict, streams: float) -> float:
    """Bytes of state one decode step has to read: every decoding
    sequence's, once."""
    return streams * state_bytes(w)


def chunk_required_ops(w: dict, tokens: float) -> float:
    """Operations the recurrence of a prefill chunk of `tokens` live
    tokens needs in its chunked form, all state layers: a token's scores
    against its sub-block a group, their product with x a head, the
    state's read and its update a head."""
    t, p, n = w["sub_block"], w["mamba_head_dim"], w["state_size"]
    per_token = (w["n_groups"] * 2 * t * n
                 + w["mamba_heads"] * (2 * t * p + 2 * 2 * p * n))
    return tokens * w["mamba_layers"] * per_token


def decode_read_bytes(context_tokens: float, kv_bytes_per_token=None) -> float:
    """Bytes of keys and values one decode step has to read: the context
    of every decoding stream, one row a position for the one attention
    layer. (The engine's `kv_bytes_per_token` also spreads a sequence's
    state over `max_len`; a step does not read that a position, so it is
    not taken.)"""
    return context_tokens * ROW_BYTES


def held_expert_bytes(w: dict) -> float:
    """Bytes of the held routed experts' weights, all expert layers: what
    a prefill chunk has to read when its tokens reach every held expert
    (512 tokens, 22 of 512 each, 128 held: a held expert gets 22 tokens
    on average and is missed with probability about e^-22)."""
    return (w["experts_held"] * expert_parameters(w) * w["value_bytes"]
            * w["expert_layers"])


def step_required_bytes(w: dict, streams: float,
                        context_tokens: float) -> dict:
    """What a decode step of `streams` decoding sequences over
    `context_tokens` cached positions has to move, by part."""
    touched = w["experts_held"] * (1 - (1 - 1 / w["router_width"]) ** (
        streams * w["experts_per_token"]))
    other = (w["mamba_layers"] * mamba_layer_parameters(w)
             + w["attention_layers"] * attention_layer_parameters(w)
             + w["expert_layers"] * expert_layer_parameters(w, 0)
             + w["vocab_size"] * w["d_model"]) * w["value_bytes"]
    return {
        "experts_touched": touched * expert_parameters(w) * w["value_bytes"]
        * w["expert_layers"],
        "states_read_and_written": 2 * state_read_bytes(w, streams),
        "other_weights": other,
        "attention_rows": decode_read_bytes(context_tokens)
        * w["attention_layers"],
    }
