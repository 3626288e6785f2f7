"""Metric arithmetic for a decoder of gated short convolutions and
grouped-head attention with routed experts and no shared one
(`benchmarks/configs/lfm2-8b-a1b.json` names this module as its `arith`):
parameters, the bytes of a sequence's tails and of a cached position, the
bytes a decode step needs and the operations a prefill chunk's attention
needs, computed from the file's own keys. Later PRs cannot change these.

Every count is of the mechanism, never of a kernel: a chunk's operations
are those of its live tokens, whatever bucket they were padded to and
however a kernel lays two heads side by side or makes a tile's scores
again; a step's bytes are the decoding sequences' rows and the weights
once, not idle slots' or a page's padding or a matrix read twice.

- the routed experts are bound by bytes in both programs: a step of 128
  rows routes 512 pairs over 32 experts and a chunk of 512 tokens 2,048,
  so either reaches every held expert (an expert is missed by a step
  with probability about e^-16) and has to read all of them once: 45
  GFLOP a layer a chunk against 705 MB, 64 operations a byte where the
  chip does 240. No formulation reads a matrix less than once.
- the attention's decode is bound by bytes: one key row and one value
  row of `num_key_value_heads` heads a cached position a layer.
- a chunk's attention is bound by operations: 512 queries against a
  thousand and more keys each read 6,144 B a position once and multiply
  4 x 32 x 64 a query a key.
"""

from __future__ import annotations

# one cached position, bfloat16: keys and values of 8 heads of 64 in each
# of the three attention layers. `decode_read_bytes` is handed no widths
# (`layer_metrics/decode_roofline.py`); benchmarks/tests hold this to the
# file's keys
ROW_BYTES = 6144


def widths(config: dict) -> dict:
    lo = config.get("layers_from", 0)
    n = config["num_hidden_layers"]
    types = config["layer_types"][lo:lo + n]
    value_bytes = 2 if config["program"]["model"]["dtype"] == "bfloat16" \
        else 4
    heads = config["num_attention_heads"]
    head_dim = config["hidden_size"] // heads
    attention = types.count("full_attention")
    dense = max(0, min(n, config["num_dense_layers"] - lo))
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": n,
        "conv_layers": types.count("conv"),
        "attention_layers": attention,
        "dense_layers": dense,
        "sparse_layers": n - dense,
        "conv_taps": config["conv_L_cache"],
        "n_heads": heads,
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": head_dim,
        "d_ff": config["intermediate_size"],
        "expert_ff": config["moe_intermediate_size"],
        "experts_held": config["num_experts"],
        "router_width": config.get("published", {}).get(
            "num_experts", config["num_experts"]),
        "experts_per_token": config["num_experts_per_tok"],
        "value_bytes": value_bytes,
        "row_bytes": 2 * config["num_key_value_heads"] * head_dim
        * value_bytes * attention,
    }


def conv_layer_parameters(w: dict) -> int:
    d = w["d_model"]
    return d * 3 * d + w["conv_taps"] * d + d * d   # W_in, the taps, W_out


def attention_layer_parameters(w: dict) -> int:
    d, hd = w["d_model"], w["head_dim"]
    return (2 * d * w["n_heads"] * hd + 2 * d * w["n_kv_heads"] * hd
            + 2 * hd)                               # q, o; k, v; two scales


def dense_mlp_parameters(w: dict) -> int:
    return 3 * w["d_model"] * w["d_ff"]


def expert_parameters(w: dict) -> int:
    return 3 * w["d_model"] * w["expert_ff"]


def sparse_ffn_parameters(w: dict, experts: int | None = None) -> int:
    """A sparse layer's feed-forward part with `experts` routed experts
    (the held ones where none is given): router and its bias beside them;
    no shared expert."""
    held = w["experts_held"] if experts is None else experts
    return (held * expert_parameters(w)
            + w["d_model"] * w["router_width"] + w["router_width"])


def parameters(w: dict) -> int:
    """As run: the layers (two norm scales each), the embedding, which is
    the head too, and the final norm."""
    d = w["d_model"]
    return (w["conv_layers"] * conv_layer_parameters(w)
            + w["attention_layers"] * attention_layer_parameters(w)
            + w["dense_layers"] * dense_mlp_parameters(w)
            + w["sparse_layers"] * sparse_ffn_parameters(w)
            + 2 * w["n_layers"] * d + w["vocab_size"] * d + d)


def tail_bytes(w: dict) -> int:
    """One sequence's convolution tails as stored: the last taps - 1
    positions' g, all convolution layers, in the activations' type;
    whatever its length."""
    return (w["conv_layers"] * (w["conv_taps"] - 1) * w["d_model"]
            * w["value_bytes"])


def decode_read_bytes(context_tokens: float, kv_bytes_per_token=None) -> float:
    """Bytes of keys and values one decode step has to read: the context
    of every decoding stream, one row a position a layer. (The engine's
    `kv_bytes_per_token` also spreads a sequence's tails over `max_len`;
    a step does not read that a position, so it is not taken.)"""
    return context_tokens * ROW_BYTES


def held_expert_bytes(w: dict) -> float:
    """Bytes of the held routed experts' weights, all sparse layers: what
    a decode step of a full batch and a prefill chunk alike have to read,
    since either reaches every held expert."""
    return (w["experts_held"] * expert_parameters(w) * w["value_bytes"]
            * w["sparse_layers"])


def chunk_attention_ops(w: dict, start: float, tokens: float) -> float:
    """Operations the attention of a prompt chunk of `tokens` live
    queries at positions `start ..` needs, all attention layers: scores
    and values inside the mask, 4 x heads x head_dim a query a key it may
    see (every position up to its own)."""
    start, tokens = int(start), int(tokens)
    seen = tokens * start + tokens * (tokens + 1) // 2
    return (4.0 * w["n_heads"] * w["head_dim"] * w["attention_layers"]
            * seen)


def step_required_bytes(w: dict, streams: float,
                        context_tokens: float) -> dict:
    """What a decode step of `streams` decoding sequences over
    `context_tokens` cached positions has to move, by part."""
    vb = w["value_bytes"]
    touched = w["experts_held"] * (1 - (1 - 1 / w["router_width"]) ** (
        streams * w["experts_per_token"]))
    return {
        "experts_touched": touched * expert_parameters(w) * vb
        * w["sparse_layers"],
        "mixers": (w["conv_layers"] * conv_layer_parameters(w)
                   + w["attention_layers"]
                   * attention_layer_parameters(w)) * vb,
        "dense_mlps_and_routers": (
            w["dense_layers"] * dense_mlp_parameters(w)
            + w["sparse_layers"] * sparse_ffn_parameters(w, 0)) * vb,
        "head": w["vocab_size"] * w["d_model"] * vb,
        "tails_read_and_written": 2 * streams * tail_bytes(w),
        "attention_rows": decode_read_bytes(context_tokens),
    }
