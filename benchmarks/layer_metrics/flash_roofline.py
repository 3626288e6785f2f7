"""Flash attention's share of its compute roofline, from the trace.

The time is that of every call of the kernels named `flash_fwd`,
`flash_dq` and `flash_dkv` (whichever of them ran: a fused backward
kernel keeps one of the names; a rematerialised forward is `flash_fwd`
too). The required work is one causal forward + dQ + dK/dV pass per
layer per optimizer step per chip: the fused dispatch's runs on all
chips x steps a dispatch x layers. Neither depends on an operand's
layout or on how many heads a kernel block holds."""

from benchmarks.harness import spans

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def read(ctx, module: str = "jit_multi"):
    s, mix, w = spans.summary(ctx), ctx["traffic"], ctx["widths"]
    if not s or module not in ctx["trace"]["modules"]:
        return None
    found = spans.kernel_seconds(s, KERNELS)
    if found is None or found[1] <= 0:
        return None
    passes = (ctx["trace"]["modules"][module][0] * mix["unroll"]
              * w["n_layers"])
    need = passes * ctx["arith"].flash_attention_flops(
        mix["batch"] // ctx["cell"]["chips"], mix["seq_len"], w["n_heads"],
        w["head_dim"], layers=1)
    return 100.0 * need / ctx["peaks"]["flops_per_s"] / found[1]
