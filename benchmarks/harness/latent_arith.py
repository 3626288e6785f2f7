"""Metric arithmetic for a decoder with a latent cache, learned sparse
attention and routed experts (`benchmarks/configs/glm-5.2.json` names this
module as its `arith`): the bytes a call needs, computed from the file's
own keys. Later PRs cannot change these.

All three kernels they describe are bound by bytes: a decode step's
attention does about 2 x 64 heads operations a byte of selected row, far
under the chip's 240 operations a byte only because the rows arrive one
DMA each; the indexer 2 x 32; an expert at a few tokens reads its whole
weight for them.
"""

from __future__ import annotations


def widths(config: dict) -> dict:
    lo = config.get("layers_from", 0)
    hi = lo + config["num_hidden_layers"]
    value_bytes = 2 if config["program"]["model"]["dtype"] == "bfloat16" \
        else 4
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "latent_row_values": config["kv_lora_rank"]
        + config["qk_rope_head_dim"],
        "index_dim": config["index_head_dim"],
        "index_topk": config["index_topk"],
        "full_layers": config["indexer_types"][lo:hi].count("full"),
        "sparse_layers": config["mlp_layer_types"][lo:hi].count("sparse"),
        "expert_ff": config["moe_intermediate_size"],
        "experts_held": config["n_routed_experts"],
        "router_width": config.get("published", {}).get(
            "n_routed_experts", config["n_routed_experts"]),
        "experts_per_token": config["num_experts_per_tok"],
        "value_bytes": value_bytes,
    }


def sparse_decode_read_bytes(widths: dict, streams: float) -> float:
    """Bytes of selected cache rows one decode step's attention has to
    read: for each decoding stream `index_topk` rows of
    `latent_row_values` values in every layer. Every stream is taken to
    be past index_topk, as every stream of a prompt longer than that is
    (a shorter one reads its context and no more: the share then reads
    high)."""
    return (streams * widths["index_topk"] * widths["latent_row_values"]
            * widths["value_bytes"] * widths["n_layers"])


def index_read_bytes(widths: dict, context_tokens: float) -> float:
    """Bytes of index keys one decode step's indexers have to read: the
    whole context of every decoding stream, one key of `index_dim` values
    a position, in every layer that owns an indexer."""
    return (context_tokens * widths["index_dim"] * widths["value_bytes"]
            * widths["full_layers"])


def held_expert_bytes(widths: dict) -> float:
    """Bytes of the held routed experts' weights, all sparse layers: what
    a prefill chunk has to read when its tokens reach every held expert
    (512 tokens, 8 of 256 each: an expert is missed with probability
    (31/32)^512 = 9e-8)."""
    return (widths["experts_held"] * 3 * widths["expert_ff"]
            * widths["d_model"] * widths["value_bytes"]
            * widths["sparse_layers"])
