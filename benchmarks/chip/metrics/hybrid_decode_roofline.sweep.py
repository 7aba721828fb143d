"""Share of the HBM roofline that the decode program (``step``)
reaches in a hybrid family: the bytes one iteration must move (the
family's ``decode_bytes``: the generator's float32 weights once, each
decoded row's Mamba2 state read and written once, each unique KV page
once per hybrid layer) over peak HBM bandwidth, divided by the
program's device time, summed over the traced window's iterations.  At
one token a row the program does a few operations per byte, far below
the chip's ratio of compute to bandwidth, so the bound is bandwidth.
A family without ``decode_bytes`` has nothing to read."""
from benchmarks.chip import trace as TR

PROGRAM = "step"


def read(ctx):
    c, fam = ctx["counters"], ctx["cell"].family
    if not c["n_decode_steps"] or not hasattr(fam, "decode_bytes"):
        return None
    secs = TR.program_s(ctx["trace"], PROGRAM)
    if not secs:
        return None
    need = fam.decode_bytes(ctx["dims"]["generator"], ctx["page_size"],
                            c["n_decode_steps"], c["n_decoded_tokens"],
                            c["unique_pages_streamed"])
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / secs
