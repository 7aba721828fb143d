"""The whole search's share of the chip's peak: the operations that
generator prefill and decode, PRM scoring and the embedder require over
their true lengths (``flops.py``), over the traced window at the
chip's bf16 peak."""


def read(ctx):
    t = ctx["trace"]
    if not t or not ctx["flops"]:
        return None
    return 100.0 * ctx["flops"] / (t["window_s"] * ctx["peak"]["bf16_flops"])
