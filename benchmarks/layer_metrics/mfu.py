"""Model FLOP/s utilization of the untraced timed window."""


def read(ctx):
    train = ctx["stats"].get("train")
    if not train:
        return None
    need = ctx["arith"].train_flops_per_token(ctx["widths"],
                                              train["seq_len"])
    return (100.0 * train["train_tokens_per_s"] * need
            / (train["chips"] * ctx["peaks"]["flops_per_s"]))
