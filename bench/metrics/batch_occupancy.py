"""Scheduler batch occupancy over the window: decode lane-steps that kept
a token over lane-steps dispatched (the schedulers' ``decoded_tokens`` and
``lane_steps`` counters, at the window's edges), in percent."""
import measure


def read(ctx):
    steps = measure.delta(ctx.rec, "lane_steps")
    if steps <= 0:
        return None
    return 100.0 * measure.delta(ctx.rec, "decoded_tokens") / steps
