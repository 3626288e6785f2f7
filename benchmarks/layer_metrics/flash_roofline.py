"""Flash attention's share of its compute roofline, from the trace.

The kernels are known by their shapes (the trace carries no kernel
name): every operand of a flash call is `bf16[B*H, T, D']` with B the
sequences on one chip and D' the head size the kernel pads to. Forward
takes 3 operands; dQ takes 6 and returns one array, dK/dV returns two.
Required work is counted per dQ call (one per layer per step per chip);
the time is that of every flash call, the forward's rematerialised
second run included.
"""

from benchmarks.harness import arith, trace as trace_mod


def read(ctx):
    trace, mix, w = ctx.get("trace"), ctx["traffic"], ctx["widths"]
    if not trace:
        return None
    local = mix["batch"] // ctx["cell"]["chips"]
    dims = rf"bf16\[{local * w['n_heads']},{mix['seq_len']},\d+\]"
    flash = rf"/pallas [^<]*<- {dims},{dims},{dims}(,|$)"
    dq = rf"/pallas {dims} <- ({dims},){{4}}"
    _, seconds = trace_mod.op_seconds(trace, flash)
    dq_calls, _ = trace_mod.op_seconds(trace, dq)
    if not dq_calls or seconds <= 0:
        return None
    need = dq_calls * arith.flash_attention_flops(
        local, mix["seq_len"], w["n_heads"], w["head_dim"], layers=1)
    return 100.0 * need / ctx["peaks"]["flops_per_s"] / seconds
