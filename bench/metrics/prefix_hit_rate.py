"""Scheduler prefix-cache hit rate over the window: prompt tokens found in
the cache over prompt tokens seated (the schedulers' ``prefix_hit_tokens``
and ``prefix_query_tokens`` counters, at the window's edges), in percent."""
import measure


def read(ctx):
    q = measure.delta(ctx.rec, "prefix_query_tokens")
    if q <= 0:
        return None
    return 100.0 * measure.delta(ctx.rec, "prefix_hit_tokens") / q
