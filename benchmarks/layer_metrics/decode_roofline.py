"""The paged decode kernel's share of its byte roofline: the cached keys
and values a decode step has to read (the context of every decoding
stream, as the clients saw it over the traced window, at the pool's
bytes a position) over the chip's memory bandwidth, over the kernel's
time a decode step. Bound by bytes: a decode step does two operations a
byte it reads."""

from benchmarks.harness import spans


def read(ctx, kernel: str = "paged_decode", module: str = "jit__decode"):
    s = spans.summary(ctx)
    serve, engine = ctx["stats"].get("serve"), ctx["stats"].get("engine")
    if not s or not serve or module not in ctx["trace"]["modules"]:
        return None
    found = spans.kernel_seconds(s, [kernel])
    if found is None or found[1] <= 0:
        return None
    step_s = found[1] / ctx["trace"]["modules"][module][0]
    need = ctx["arith"].decode_read_bytes(
        serve["decoding_context_tokens"], engine["kv_bytes_per_token"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / step_s
