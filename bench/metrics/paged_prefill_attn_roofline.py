"""Paged chunked-prefill attention kernel: the least time its work needs
on this chip (each chunk's prefix K/V read once, its queries in and outputs
out, causal scores; ``bench/flops.py``; compute- or memory-bound, whichever
bounds), over the kernel's device time in the trace, in percent."""
import flops

# the only Pallas kernel in the program
PROGRAM = "jit_chunk"


def read(ctx):
    if ctx.trace is None:
        return None
    secs = ctx.kernel_seconds(PROGRAM)
    chunks = ctx.prefill_chunks()
    if secs <= 0 or not chunks:
        return None
    f = b = 0.0
    for arm, start, n in chunks:
        cf, cb = flops.prefill_attn_cost(ctx.dims(arm), start, n)
        f += cf
        b += cb
    return flops.roofline_share(f, b, secs, ctx.peak)[0]
