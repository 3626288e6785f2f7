"""Metric arithmetic for a decoder whose sequence mixer is power retention
(`benchmarks/configs/brumby-14b.json` names this module as its `arith`):
the parameters, the bytes of a sequence's state, the bytes a decode step
needs and the operations a prefill chunk needs, computed from the file's
own keys. Later PRs cannot change these.

Every count is of the mechanism, never of a kernel: the state's feature
dimension is the exact symmetric second power of a head's dims,
D = d (d + 1) / 2 (8256 at d = 128), whatever layout a kernel pads it to;
a chunk's operations are those of its live tokens, whatever bucket they
were padded to and however the kernel tiles or splits its operands.

- The step is bound by bytes: a decode step has to read every decoding
  sequence's whole state once, in every layer. A read-modify-write reads
  it and writes it, so it can reach 50 % of this; a formulation that
  writes less can approach 100 % and cannot pass it, because no
  formulation reads less than the state once.
- The chunk is bound by operations: a token updates a key-value head's
  state (D x (d + 1) multiply-adds: the d value columns and the
  normaliser's one) and each of the group's query heads reads it
  (another D x (d + 1) each). The chunk form replaces a token's read by
  one matmul a chunk plus the masked square inside the chunk; the square
  is the formulation's, the state's part is what any formulation does, so
  only that is counted: 2 x (group + 1) x D x (d + 1) a token a key-value
  head a layer (0.10 GFLOP a token a layer at the published widths).
"""

from __future__ import annotations


def widths(config: dict) -> dict:
    d = config["head_dim"]
    return {
        "vocab_size": config["vocab_size"],
        "d_model": config["hidden_size"],
        "n_layers": config["num_hidden_layers"],
        "n_heads": config["num_attention_heads"],
        "n_kv_heads": config["num_key_value_heads"],
        "head_dim": d,
        "feature_dim": d * (d + 1) // 2,
        "d_ff": config["intermediate_size"],
        "state_bytes_per_value": 4,
        "value_bytes": 2 if config["program"]["model"]["dtype"] == "bfloat16"
        else 4,
    }


def layer_parameters(widths: dict) -> int:
    d, hd, f = widths["d_model"], widths["head_dim"], widths["d_ff"]
    hq, hkv = widths["n_heads"], widths["n_kv_heads"]
    return (2 * d * hq * hd + 2 * d * hkv * hd      # q, o; k, v
            + d * hkv + hkv                          # the gate and its bias
            + 3 * d * f + 2 * d + 2 * hd)            # MLP; four norm scales


def parameters(widths: dict) -> int:
    """As run: the layers, embedding and head both, the final norm."""
    return (widths["n_layers"] * layer_parameters(widths)
            + 2 * widths["vocab_size"] * widths["d_model"]
            + widths["d_model"])


def state_bytes(widths: dict) -> int:
    """One sequence's state, all layers: S [d, D] and z [D] a key-value
    head, float32."""
    return (widths["n_layers"] * widths["n_kv_heads"]
            * widths["feature_dim"] * (widths["head_dim"] + 1)
            * widths["state_bytes_per_value"])


def state_read_bytes(widths: dict, streams: float) -> float:
    """Bytes of state one decode step has to read: every decoding
    sequence's, once."""
    return streams * state_bytes(widths)


def step_weight_bytes(widths: dict) -> int:
    """Bytes of weights a decode step reads: the layers and the head (the
    embedding gives one row a sequence)."""
    return (widths["n_layers"] * layer_parameters(widths)
            + widths["vocab_size"] * widths["d_model"]) * widths["value_bytes"]


def chunk_required_ops(widths: dict, tokens: float) -> float:
    """Operations the retention of a prefill chunk of `tokens` live
    tokens needs, all layers: the state's update and the group's reads."""
    group = widths["n_heads"] // widths["n_kv_heads"]
    per_head = 2 * (group + 1) * widths["feature_dim"] \
        * (widths["head_dim"] + 1)
    return tokens * widths["n_layers"] * widths["n_kv_heads"] * per_head
