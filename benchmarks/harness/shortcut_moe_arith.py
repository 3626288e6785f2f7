"""Metric arithmetic for a decoder of two latent-attention blocks and two
dense MLPs a layer with the routed experts on a shortcut beside them and
a softmax router whose last outputs are identity experts
(`benchmarks/configs/longcat-flash-chat.json` names this module as its
`arith`): parameters, the bytes of a cached position, the bytes a decode
step needs, the operations a prefill chunk's attention needs and the
experts a step or a chunk can be expected to reach, computed from the
file's own keys. Later PRs cannot change these.

Every count is of the mechanism, never of a kernel: a chunk's operations
are those of its live tokens, whatever bucket they were padded to; a
step's bytes are the decoding sequences' rows and the weights once, not
idle slots' or a row's padding to whole lane tiles or a matrix read
twice.

- latent decode is bound by bytes, by a factor of two only: one row of
  `kv_lora_rank + qk_rope_head_dim` values a cached position a block,
  and 64 heads x (576 + 512) x 2 operations on it, 121 a byte where the
  chip does 240.
- a chunk's latent attention is bound by operations. Two formulations
  compute it and the need is the lesser: absorbed (the query through the
  key up-projection once, then `heads x (row + kv_lora_rank) x 2` a query
  a key) or expanded (every context row's keys and values rebuilt once,
  `2 x kv_lora_rank x heads x (nope + v)` a key, then `heads x (nope +
  rope + v) x 2` a query a key). At 512 queries the rebuild is paid off
  and the expanded form is the lesser; a short last chunk deep in a long
  prompt (64 queries over 3,000 rows) is cheaper absorbed.
- the routed experts are bound by bytes in both programs, and what has
  to be read is the experts that got a pair: a choice names a held
  expert with probability `held / router_width`' share of the `moe_topk`
  (uniform routing: the identity outputs take their third of the choices
  and cost nothing), so a step of 64 rows reaches about 10 of 16 and a
  chunk of 512 all of them.
"""

from __future__ import annotations

# one cached position, bfloat16: (512 + 64) x 2 B in each of the eight
# attention blocks of the four layers. `decode_read_bytes` is handed no
# widths (`layer_metrics/decode_roofline.py`); benchmarks/tests hold this
# to the file's keys
ROW_BYTES = 9216
LANES = 128             # a stored row is whole lane tiles of 32-bit words


def widths(config: dict) -> dict:
    value_bytes = 2 if config["program"]["model"]["dtype"] == "bfloat16" \
        else 4
    row_values = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    layers = config["num_layers"]
    routed = config.get("published", {}).get("n_routed_experts",
                                             config["n_routed_experts"])
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": layers,
        "attention_blocks": 2 * layers,
        "n_heads": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"],
        "kv_rank": config["kv_lora_rank"],
        "nope_dim": config["qk_nope_head_dim"],
        "rope_dim": config["qk_rope_head_dim"],
        "v_dim": config["v_head_dim"],
        "latent_row_values": row_values,
        "d_ff": config["ffn_hidden_size"],
        "expert_ff": config["expert_ffn_hidden_size"],
        "experts_held": config["n_routed_experts"],
        "routed_width": routed,
        "identity_experts": config["zero_expert_num"],
        "router_width": routed + config["zero_expert_num"],
        "experts_per_token": config["moe_topk"],
        "value_bytes": value_bytes,
        "row_bytes": row_values * value_bytes * 2 * layers,
    }


def latent_block_parameters(w: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o and the two low-rank norms."""
    d, nh = w["d_model"], w["n_heads"]
    return (d * w["q_rank"]
            + w["q_rank"] * nh * (w["nope_dim"] + w["rope_dim"])
            + d * w["latent_row_values"]
            + w["kv_rank"] * nh * (w["nope_dim"] + w["v_dim"])
            + nh * w["v_dim"] * d + w["q_rank"] + w["kv_rank"])


def dense_mlp_parameters(w: dict) -> int:
    return 3 * w["d_model"] * w["d_ff"]


def expert_parameters(w: dict) -> int:
    return 3 * w["d_model"] * w["expert_ff"]


def layer_parameters_outside_experts(w: dict) -> int:
    """Two attention blocks, two MLPs, the four norms of D, the router
    and its bias."""
    d = w["d_model"]
    return (2 * latent_block_parameters(w) + 2 * dense_mlp_parameters(w)
            + 4 * d + d * w["router_width"] + w["router_width"])


def parameters(w: dict, experts: int | None = None,
               layers: int | None = None, vocab: int | None = None) -> int:
    """As run (or with `experts` routed experts a layer, `layers` layers
    and `vocab` rows: the published model's count): the layers, embedding
    and head both, the final norm."""
    held = w["experts_held"] if experts is None else experts
    n = w["n_layers"] if layers is None else layers
    v = w["vocab_size"] if vocab is None else vocab
    return (n * (layer_parameters_outside_experts(w)
                 + held * expert_parameters(w))
            + 2 * v * w["d_model"] + w["d_model"])


def stored_row_bytes(w: dict) -> int:
    """One cached position as the pool keeps it: a row of every attention
    block, each padded to whole lane tiles of 32-bit words
    (`ops.sparse_latent.row_words`)."""
    words = -(-w["latent_row_values"] * w["value_bytes"] // 4)
    return -(-words // LANES) * LANES * 4 * w["attention_blocks"]


def pool_pages(slots: int, max_len: int, block_size: int) -> int:
    """Every slot's longest request and the trash page."""
    return slots * -(-max_len // block_size) + 1


def decode_read_bytes(context_tokens: float, kv_bytes_per_token=None) -> float:
    """Bytes of latent rows one decode step has to read: the context of
    every decoding stream, one row a position in each of the eight
    attention blocks. (The engine's `kv_bytes_per_token` counts a row's
    padding to whole lane tiles too; a step need not read that, so it is
    not taken.)"""
    return context_tokens * ROW_BYTES


def expected_held_pairs(w: dict, rows: float) -> float:
    """Pairs the held experts get of `rows` tokens a layer, under uniform
    routing over the router's whole width."""
    return rows * w["experts_per_token"] * w["experts_held"] \
        / w["router_width"]


def expected_experts_reached(w: dict, rows: float) -> float:
    """Held experts that get at least one pair of `rows` tokens a layer:
    a token names a given output with probability `moe_topk /
    router_width`, its choices being distinct."""
    miss = (1.0 - w["experts_per_token"] / w["router_width"]) ** rows
    return w["experts_held"] * (1.0 - miss)


def held_expert_bytes(w: dict) -> float:
    """Bytes of the held routed experts' weights, all layers: what a call
    that reaches every held expert has to read."""
    return (w["experts_held"] * expert_parameters(w) * w["value_bytes"]
            * w["n_layers"])


def expected_expert_bytes(w: dict, rows: float) -> float:
    """Bytes of the experts that a step or a chunk of `rows` live tokens
    can be expected to reach, all layers, each matrix once: the least
    any kernel reads, so a share of it cannot pass 100 % but by the
    routing's own skew."""
    return (expected_experts_reached(w, rows) * expert_parameters(w)
            * w["value_bytes"] * w["n_layers"])


def chunk_attention_ops(w: dict, start: float, tokens: float) -> float:
    """Operations the latent attention of a prompt chunk of `tokens` live
    queries at positions `start ..` needs, all attention blocks: scores
    and values inside the mask (every position up to a query's own), in
    the cheaper of the absorbed and the expanded form (the module's
    header)."""
    start, tokens = int(start), int(tokens)
    nh = w["n_heads"]
    seen = tokens * start + tokens * (tokens + 1) // 2
    absorbed = (2.0 * nh * (w["latent_row_values"] + w["kv_rank"]) * seen
                + 2.0 * tokens * nh * w["kv_rank"]
                * (w["nope_dim"] + w["v_dim"]))
    expanded = (2.0 * nh * (w["nope_dim"] + w["rope_dim"] + w["v_dim"]) * seen
                + 2.0 * (start + tokens) * w["kv_rank"] * nh
                * (w["nope_dim"] + w["v_dim"]))
    return min(absorbed, expanded) * w["attention_blocks"]


def step_required_bytes(w: dict, streams: float,
                        context_tokens: float) -> dict:
    """What a decode step of `streams` decoding sequences over
    `context_tokens` cached positions has to move, by part."""
    vb = w["value_bytes"]
    return {
        "experts_reached": expected_expert_bytes(w, streams),
        "outside_the_experts": w["n_layers"]
        * layer_parameters_outside_experts(w) * vb,
        "head": w["vocab_size"] * w["d_model"] * vb,
        "latent_rows": decode_read_bytes(context_tokens),
    }
