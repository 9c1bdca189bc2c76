"""What a per-layer metric reader is given, and the arithmetic they share.

A reader is ``bench/metrics/<metric>.py`` with ``read(ctx) -> float|None``;
it returns None where its run has nothing for it to read (no trace, no
such program in the trace), and the harness then leaves the metric out.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import measure
from reference.dense import branch_dims


@dataclass
class Context:
    cell: object          # cells.Cell
    rec: object           # drive.Record
    trace: Optional[dict]  # trace_reduce.reduce(...) or None
    peak: dict            # bench/peaks.json entry of this device

    def dims(self, arm: int) -> dict:
        return branch_dims(self.cell.config, arm)

    def traced(self, t: float) -> bool:
        return self.rec.trace_t0 <= t < self.rec.trace_t1

    def program_seconds(self, prefix: str) -> float:
        """Device seconds of the jitted programs named ``prefix*``."""
        return sum(v for k, v in self.trace["programs"].items()
                   if k.startswith(prefix))

    def kernel_seconds(self, program: str) -> float:
        """Device seconds of the Pallas kernels inside program ``program``
        (the trace names a kernel only as a ``tpu_custom_call``)."""
        return self.trace["kernels"].get(program, 0.0)

    def decode_tokens(self):
        """(arm, context) of every decoded token in the traced window."""
        out = []
        for r in self.rec.requests.values():
            for t, ctx in measure.decode_tokens(r):
                if self.traced(t):
                    out.append((r["arm"], ctx))
        return out

    def prefill_chunks(self):
        """(arm, start, n_tok) of every prefill chunk in the traced
        window."""
        out = []
        for r in self.rec.requests.values():
            for t, s, n in measure.prefill_chunks(self.rec, r,
                                                  self.rec.prefill_chunk):
                if self.traced(t):
                    out.append((r["arm"], s, n))
        return out

    def trace_delta(self, key: str) -> float:
        return float(self.rec.trace_edge1.get(key, 0)
                     - self.rec.trace_edge0.get(key, 0))


def idle_share(ctx: Context):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]


# -- readers shared by metrics split per cell (``<name>.chat``/``.batch``)
def decode_step_ms(ctx: Context):
    """Device time of the fused decode-scan programs (``jit_decode``) over
    the scan steps they ran (each ``decode:WxK`` dispatch runs K steps)."""
    if ctx.trace is None:
        return None
    secs = ctx.program_seconds("jit_decode")
    steps = sum(int(d.split("x")[1]) for d in ctx.trace["dispatches"]
                if d.startswith("decode:"))
    if secs <= 0 or steps == 0:
        return None
    return 1e3 * secs / steps


def decode_attn_roofline(ctx: Context):
    """The paged decode attention kernel (the only Pallas kernel in
    ``jit_decode``): the least time its work needs on this chip (each
    active lane's K/V over its own length read once, plus queries and
    outputs) over its device time, in percent."""
    import flops
    if ctx.trace is None:
        return None
    secs = ctx.kernel_seconds("jit_decode")
    toks = ctx.decode_tokens()
    if secs <= 0 or not toks:
        return None
    f = b = 0.0
    for arm, context in toks:
        df, db = flops.decode_attn_cost(ctx.dims(arm), context)
        f += df
        b += db
    return flops.roofline_share(f, b, secs, ctx.peak)[0]


def step_mfu(ctx: Context):
    """Model FLOPs of every token processed in the traced window (2 per
    matmul weight of its arm plus attention at its context) over the
    traced window's seconds times the chip's peak, in percent."""
    import flops
    if ctx.trace is None:
        return None
    total = sum(flops.token_flops(ctx.dims(arm), c)
                for arm, c in ctx.decode_tokens())
    for arm, start, n in ctx.prefill_chunks():
        total += sum(flops.token_flops(ctx.dims(arm), p + 1)
                     for p in range(start, start + n))
    if total <= 0:
        return None
    return 100.0 * total / (ctx.trace["window_s"]
                            * ctx.peak["bf16_flops_per_s"])
