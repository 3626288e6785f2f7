"""Metric arithmetic for a decoder whose every layer holds a Mamba-2 state
branch and a grouped-head attention branch side by side, then a gated MLP
(`benchmarks/configs/falcon-h1-34b.json` names this module as its
`arith`): parameters, the bytes of a sequence's state and of a cached
position, the bytes a decode step needs and the operations a prefill
chunk needs, computed from the file's own keys. Later PRs cannot change
these.

Every count is of the mechanism, never of a kernel: a chunk's operations
are those of its live tokens, whatever bucket they were padded to and
however a kernel splits its operands, pads a group of query heads or
makes a group's scores again; a step's bytes are the decoding sequences'
states and rows, not idle slots' or a page's padding.

- `mamba2_step` is bound by bytes: a decode step has to read every
  decoding sequence's whole state once, in every layer (H x P x N
  float32). A read-modify-write reads it and writes it, so it can reach
  50 % of this; no formulation reads less than the state once (the same
  bound `mamba_moe_arith` states).
- `mamba2_chunk` is bound by operations, those of the chunked form (the
  state-space duality) at sub-blocks of `mamba_chunk_size`: a live
  token's scores `C B^T` against its sub-block (N multiply-adds a pair of
  positions, made once a group), their product with `x` (P a pair a
  head), the read of the carried state (`C S`: P N a head) and its update
  (`B^T x`: P N a head). The sub-block's triangle is counted whole, as
  the matmul makes it.
- the attention's decode is bound by bytes: one key row and one value row
  of `num_key_value_heads` heads a cached position a layer; a chunk's
  attention by operations, 4 x heads x head_dim a query a key it may see.
"""

from __future__ import annotations

# one cached position over the six layers that run, bfloat16: keys and
# values of 4 heads of 128 a layer (2,048 B). `decode_read_bytes` is
# handed no widths (`layer_metrics/decode_roofline.py`); benchmarks/tests
# hold this to the file's keys
ROW_BYTES = 12288


def widths(config: dict) -> dict:
    value_bytes = 2 if config["program"]["model"]["dtype"] == "bfloat16" \
        else 4
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    groups, state = config["mamba_n_groups"], config["mamba_d_state"]
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "mamba_heads": heads,
        "mamba_head_dim": p,
        "n_groups": groups,
        "state_size": state,
        "inner": heads * p,
        "conv_channels": heads * p + 2 * groups * state,
        "conv_taps": config["mamba_d_conv"],
        "sub_block": config["mamba_chunk_size"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "d_ff": config["intermediate_size"],
        "state_bytes_per_value": 4,
        "value_bytes": value_bytes,
        # one layer's cached position: keys and values
        "row_bytes": 2 * config["num_key_value_heads"] * config["head_dim"]
        * value_bytes,
    }


def state_branch_parameters(w: dict) -> int:
    d, inner, ch = w["d_model"], w["inner"], w["conv_channels"]
    return (d * (inner + ch + w["mamba_heads"])     # W_in: z, xBC, dt
            + (w["conv_taps"] + 1) * ch             # the taps and their bias
            + 3 * w["mamba_heads"]                  # dt_bias, A_log, D
            + inner                                 # the grouped norm's scale
            + inner * d)                            # W_out


def attention_branch_parameters(w: dict) -> int:
    d, hd = w["d_model"], w["head_dim"]
    return 2 * d * w["n_heads"] * hd + 2 * d * w["n_kv_heads"] * hd


def mlp_parameters(w: dict) -> int:
    return 3 * w["d_model"] * w["d_ff"]


def layer_parameters(w: dict) -> int:
    """Both branches, the MLP and the two norms' scales."""
    return (state_branch_parameters(w) + attention_branch_parameters(w)
            + mlp_parameters(w) + 2 * w["d_model"])


def parameters(w: dict) -> int:
    """As run: the layers, embedding and head both, the final norm."""
    d = w["d_model"]
    return w["n_layers"] * layer_parameters(w) + 2 * w["vocab_size"] * d + d


def state_bytes(w: dict) -> int:
    """One sequence's recurrent states, all layers: S [P, N] a head,
    float32."""
    return (w["n_layers"] * w["mamba_heads"] * w["mamba_head_dim"]
            * w["state_size"] * w["state_bytes_per_value"])


def tail_bytes(w: dict) -> int:
    """One sequence's convolution tails as stored: the last taps - 1
    positions of xBC, all layers, float32 bytes."""
    return (w["n_layers"] * (w["conv_taps"] - 1) * w["conv_channels"]
            * w["state_bytes_per_value"])


def state_read_bytes(w: dict, streams: float) -> float:
    """Bytes of state one decode step has to read: every decoding
    sequence's, once."""
    return streams * state_bytes(w)


def chunk_required_ops(w: dict, tokens: float) -> float:
    """Operations the recurrence of a prefill chunk of `tokens` live
    tokens needs in its chunked form, all layers: a token's scores
    against its sub-block a group, their product with x a head, the
    state's read and its update a head."""
    t, p, n = w["sub_block"], w["mamba_head_dim"], w["state_size"]
    per_token = (w["n_groups"] * 2 * t * n
                 + w["mamba_heads"] * (2 * t * p + 2 * 2 * p * n))
    return tokens * w["n_layers"] * per_token


def decode_read_bytes(context_tokens: float, kv_bytes_per_token=None) -> float:
    """Bytes of keys and values one decode step has to read: the context
    of every decoding stream, one row a position a layer. (The engine's
    `kv_bytes_per_token` also spreads a sequence's state over `max_len`;
    a step does not read that a position, so it is not taken.)"""
    return context_tokens * ROW_BYTES


def chunk_attention_ops(w: dict, start: float, tokens: float) -> float:
    """Operations the attention of a prompt chunk of `tokens` live
    queries at positions `start ..` needs, all layers: scores and values
    inside the mask, 4 x heads x head_dim a query a key it may see
    (every position up to its own)."""
    start, tokens = int(start), int(tokens)
    seen = tokens * start + tokens * (tokens + 1) // 2
    return 4.0 * w["n_heads"] * w["head_dim"] * w["n_layers"] * seen


def step_required_bytes(w: dict, streams: float,
                        context_tokens: float) -> dict:
    """What a decode step of `streams` decoding sequences over
    `context_tokens` cached positions has to move, by part."""
    vb = w["value_bytes"]
    return {
        "mlp_weights": w["n_layers"] * mlp_parameters(w) * vb,
        "head": w["vocab_size"] * w["d_model"] * vb,
        "branch_projections": w["n_layers"] * (
            state_branch_parameters(w) + attention_branch_parameters(w)) * vb,
        "states_read_and_written": 2 * state_read_bytes(w, streams),
        "attention_rows": decode_read_bytes(context_tokens),
    }
