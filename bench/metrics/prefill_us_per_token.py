"""Paged forward, prefill: device time of the chunked-prefill programs in
the trace over the prompt tokens they committed (prompt tokens seated but
not found in the prefix cache, from the schedulers' counters at the traced
window's edges)."""
PROGRAM = "jit_chunk"


def read(ctx):
    if ctx.trace is None:
        return None
    secs = ctx.program_seconds(PROGRAM)
    toks = (ctx.trace_delta("prefix_query_tokens")
            - ctx.trace_delta("prefix_hit_tokens"))
    if secs <= 0 or toks <= 0:
        return None
    return 1e6 * secs / toks
