"""An arithmetic module of a made-up configuration (grouped heads, untied
output matrix, experts), named by `"arith": "tests.arith_madeup"`: what
a later `model_config` PR brings beside its configuration file."""

from benchmarks.harness import arith as dense


def widths(config: dict) -> dict:
    return {**dense.widths(config),
            "experts_per_token": config["num_experts_per_tok"],
            "d_expert": config["moe_intermediate_size"]}


def train_flops_per_token(w: dict, seq_len: int) -> float:
    """The dense count with the feed-forward replaced by the experts a
    token visits."""
    ffn = 3 * w["d_model"] * w["d_ff"]
    experts = 3 * w["d_model"] * w["d_expert"] * w["experts_per_token"]
    return (dense.train_flops_per_token(w, seq_len)
            + 3.0 * w["n_layers"] * 2 * (experts - ffn))


flash_attention_flops = dense.flash_attention_flops
