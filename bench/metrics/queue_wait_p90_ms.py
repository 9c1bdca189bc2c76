"""Engine queue wait, 90th percentile: from when a request was due to when
the scheduler seated it in a lane (``Outcome.queue_wait_s`` plus the lag
between the due time and the harness's submit), over the window's served
requests."""
import measure


def read(ctx):
    waits = [r["join"] - r["due"] for r in measure.window_requests(ctx.rec)
             if measure.served(r)]
    v = measure.pct(waits, 90)
    return None if v is None else 1e3 * v
