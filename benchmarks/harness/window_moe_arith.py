"""Metric arithmetic for a decoder whose attention layers differ in how
far back they keep: grouped key-value heads, window layers and full
layers in a period, routed experts beside shared ones
(`benchmarks/configs/command-a-plus.json` names this module as its
`arith`): parameters, the bytes of a cached position, the bytes a decode
step needs of each kind of layer and the operations a prompt chunk's
attention needs, computed from the file's own keys. Later PRs cannot
change these.

Every count is of the mechanism, never of a kernel: a step's bytes are
the rows the decoding sequences' masks let them see, not idle slots'
pages or the part of a page the window has left; a chunk's operations
are those of its live queries against the keys each may see, whatever
bucket it was padded to and whatever a kernel scores and masks away.

- `gqa_full_decode` and `gqa_window_decode` are bound by bytes: a key or
  a value row of one key-value head is read once for the 16 query heads
  that share it, 4 x 16 = 64 operations a 2-byte value, under the chip's
  240 operations a byte.
- `gqa_full_chunk` and `gqa_window_chunk` are bound by operations: a
  page is read once for 512 queries of 16 heads.
- The expert kernels' functions keep the names `latent_arith` gives them.
"""

from __future__ import annotations

# one cached position of one layer, bfloat16: keys and values of 8 heads
# of 128. `decode_read_bytes` is handed no widths (`layer_metrics/
# decode_roofline.py`); benchmarks/tests hold this to the file's keys
ROW_BYTES = 4096


def widths(config: dict) -> dict:
    lo = config.get("layers_from", 0)
    n = config["num_hidden_layers"]
    kinds = config["layer_types"][lo:lo + n]
    value_bytes = 2 if config["program"]["model"]["dtype"] == "bfloat16" \
        else 4
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
        "expert_ff": config["intermediate_size"],
        "shared_experts": config["num_shared_experts"],
        "experts_held": config["num_experts"],
        "router_width": config.get("published", {}).get(
            "num_experts", config["num_experts"]),
        "experts_per_token": config["num_experts_per_tok"],
        "value_bytes": value_bytes,
        "row_bytes": 2 * config["num_key_value_heads"] * config["head_dim"]
        * value_bytes,
    }


def attention_parameters(w: dict) -> int:
    d, hd = w["d_model"], w["head_dim"]
    return 2 * d * w["n_heads"] * hd + 2 * d * w["n_kv_heads"] * hd


def expert_parameters(w: dict) -> int:
    return 3 * w["expert_ff"] * w["d_model"]


def layer_parameters(w: dict) -> int:
    """A layer as held here: attention, the shared experts and the
    router whole, the one norm, the held routed experts."""
    return (attention_parameters(w)
            + (w["shared_experts"] + w["experts_held"])
            * expert_parameters(w)
            + w["d_model"] * w["router_width"] + w["d_model"])


def parameters(w: dict) -> int:
    """As run: the layers, the embedding once (the head is tied to it),
    the final norm."""
    return (w["n_layers"] * layer_parameters(w)
            + w["vocab_size"] * w["d_model"] + w["d_model"])


def decode_read_bytes(context_tokens: float, kv_bytes_per_token=None) -> float:
    """Bytes of keys and values one decode step's full layer has to
    read: the context of every decoding stream, one row a position.
    (The engine's `kv_bytes_per_token` counts a window layer's row like a
    full layer's, which a sequence past the window no longer pays a
    position, so it is not taken.)"""
    return context_tokens * ROW_BYTES


def window_read_bytes(w: dict, rows: float) -> float:
    """Bytes one decode step's window layers have to read, `rows` being
    the sum over the decoding streams of min(context, window): what their
    masks let them see, a row a position a window layer. The engine puts
    that sum on the step's span (`bounded_rows`); no sum of contexts
    gives a minimum a stream."""
    return rows * w["row_bytes"] * w["window_layers"]


def chunk_attention_ops(w: dict, start: float, tokens: float) -> float:
    """Operations the attention of a prompt chunk of `tokens` live
    queries at positions `start ..` needs, all layers: scores and values
    inside the mask, 4 x heads x head_dim a query a key it may see (a
    full layer: every position up to its own; a window layer: the last
    `window` of them)."""
    start, tokens = int(start), int(tokens)
    full = tokens * start + tokens * (tokens + 1) // 2
    # the queries still wholly inside the window see `position + 1` keys
    short = max(0, min(tokens, w["window"] - start))
    window = (short * start + short * (short + 1) // 2
              + (tokens - short) * w["window"])
    return 4.0 * w["n_heads"] * w["head_dim"] * (
        w["full_layers"] * full + w["window_layers"] * window)


def held_expert_bytes(w: dict) -> float:
    """Bytes of the held routed experts' weights, all layers: what a
    prefill chunk has to read when its tokens reach every held expert
    (512 tokens, 8 of 128 each: a held expert gets 32 on average and is
    missed with probability e^-32)."""
    return (w["experts_held"] * expert_parameters(w) * w["value_bytes"]
            * w["n_layers"])


def pool_bytes(w: dict, pages: int, ring_pages: int, block_size: int) -> dict:
    """Bytes of the two kinds of page: `pages` that grow (the full
    layers') and `ring_pages` that do not (the window layers'), beside
    what the same `pages` would take were every layer kept to the end."""
    page = block_size * w["row_bytes"]
    return {"full": w["full_layers"] * pages * page,
            "window": w["window_layers"] * ring_pages * page,
            "unwindowed": w["n_layers"] * pages * page}


def step_required_bytes(w: dict, streams: float, context_tokens: float,
                        window_rows: float) -> dict:
    """What a decode step of `streams` decoding sequences has to move,
    by part."""
    touched = w["experts_held"] * (1 - (1 - 1 / w["router_width"]) ** (
        streams * w["experts_per_token"]))
    per_layer = (attention_parameters(w)
                 + w["shared_experts"] * expert_parameters(w)
                 + w["d_model"] * w["router_width"])
    return {
        "experts_touched": touched * expert_parameters(w) * w["value_bytes"]
        * w["n_layers"],
        "other_weights": (w["n_layers"] * per_layer
                          + w["vocab_size"] * w["d_model"])
        * w["value_bytes"],
        "full_rows": decode_read_bytes(context_tokens) * w["full_layers"],
        "window_rows": window_read_bytes(w, window_rows),
    }
