"""Share of its roofline the tree-attention kernel reaches: the bytes
it must move (every unique KV page once per layer and iteration, each
row's query in and output out, at float32) over peak HBM bandwidth,
divided by the kernel's device time in the traced window.  The kernel
does about one multiply-add per byte, far below the chip's ratio of
compute to bandwidth, so the bound is bandwidth."""
from benchmarks.chip import flops as F
from benchmarks.chip import trace as TR

KERNEL = "tree_attention"


def read(ctx):
    c = ctx["counters"]
    secs = TR.kernel_s(ctx["trace"], KERNEL) if c["n_decode_steps"] else None
    if not secs:
        return None
    need = F.tree_attention_bytes(ctx["dims"]["generator"],
                                  ctx["page_size"],
                                  c["unique_pages_streamed"],
                                  c["n_decoded_tokens"])
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / secs
