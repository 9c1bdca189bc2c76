"""Prefill programs: model FLOPs of the prompt tokens committed in the
traced window over the chunked-prefill programs' device time times the
chip's peak, in percent."""
import flops

PROGRAM = "jit_chunk"


def read(ctx):
    if ctx.trace is None:
        return None
    secs = ctx.program_seconds(PROGRAM)
    total = 0.0
    for arm, start, n in ctx.prefill_chunks():
        total += sum(flops.token_flops(ctx.dims(arm), p + 1)
                     for p in range(start, start + n))
    if secs <= 0 or total <= 0:
        return None
    return 100.0 * total / (secs * ctx.peak["bf16_flops_per_s"])
