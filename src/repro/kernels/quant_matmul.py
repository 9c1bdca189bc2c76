"""Blockwise-scaled int8 / int4 weight matmul — Pallas TPU kernel.

Weights are quantized symmetrically per (contraction group, output column):
the contraction axis D is cut into groups of ``group`` rows (128 by default,
clipped to a power-of-two divisor of D for small dims) and every
(group, column) cell carries one f32 scale ``amax / qmax``.  The kernel
streams the contraction axis one group slab at a time and dequantizes
*in register*: because the scale is constant over a slab, the slab product
can be computed on the integer codes and scaled once on the way into the
f32 accumulator — the weight matrix is never materialized in f32.

int4 packs two codes per int8 byte *within* a group: the low nibble holds
rows ``[g*G, g*G + G/2)`` and the high nibble rows ``[g*G + G/2, (g+1)*G)``,
so a group's packed slab is still one contiguous row range and sign
extension is two int8 shifts (``(p << 4) >> 4`` / ``p >> 4``).

Validated in interpret mode against the pure-jnp dequant reference
(``repro.kernels.ref.quant_matmul_ref``) like every other kernel here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def fit_group(d: int, group: int = 128) -> int:
    """Largest power-of-two divisor of ``d`` that is <= ``group`` — the
    per-128-column default degrades gracefully for small model dims."""
    g = min(group, d)
    while d % g:
        g //= 2
    return max(g, 1)


def quantize_blockwise(w, *, bits: int = 8, group: int = 128):
    """Symmetric blockwise quantization of ``w`` [..., D, E].

    Returns ``(q, scales)``: int8 codes (``[..., D, E]`` for int8;
    nibble-packed ``[..., D//2, E]`` for int4) and f32 scales
    ``[..., D//g, E]`` with ``g = fit_group(D, group)``.  Zero groups get a
    zero scale (their codes are zero, so dequantization is exact).
    """
    if bits not in (8, 4):
        raise ValueError(f"bits={bits}; expected 8 or 4")
    *lead, d, e = w.shape
    g = fit_group(d, group)
    if bits == 4 and g < 2:
        raise ValueError(f"int4 needs group >= 2 (D={d})")
    n_g = d // g
    qmax = 127 if bits == 8 else 7
    wg = w.astype(jnp.float32).reshape(*lead, n_g, g, e)
    amax = jnp.max(jnp.abs(wg), axis=-2)                     # [..., n_g, E]
    scale = amax / qmax
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(wg / safe[..., None, :]), -qmax, qmax) \
        .astype(jnp.int8)
    if bits == 4:
        half = g // 2
        lo = q[..., :half, :]
        hi = q[..., half:, :]
        q = ((hi << 4) | (lo & 0xF)).astype(jnp.int8) \
            .reshape(*lead, d // 2, e)
    else:
        q = q.reshape(*lead, d, e)
    return q, scale


def unpack_int4(p):
    """Split nibble-packed codes [..., n_g, G/2, E] into (lo, hi) int8
    slabs — arithmetic int8 shifts sign-extend the 4-bit codes."""
    lo = (p << 4) >> 4
    hi = p >> 4
    return lo, hi


def dequantize_blockwise(q, scales, *, bits: int = 8):
    """Inverse of :func:`quantize_blockwise` — returns f32 [..., D, E]."""
    *lead, dq, e = q.shape
    n_g = scales.shape[-2]
    if bits == 4:
        half = (2 * dq) // n_g // 2
        p = q.reshape(*lead, n_g, half, e)
        lo, hi = unpack_int4(p)
        full = jnp.concatenate([lo, hi], axis=-2)            # [.., n_g, G, E]
    else:
        full = q.reshape(*lead, n_g, dq // n_g, e)
    deq = full.astype(jnp.float32) * scales[..., None, :]
    return deq.reshape(*lead, n_g * full.shape[-2], e)


def infer_bits(d: int, q) -> int:
    """4 when the code matrix holds two rows per byte, else 8."""
    return 4 if q.shape[-2] * 2 == d else 8


def _fit_block(n: int, block: int) -> int:
    b = min(block, n)
    while n % b:
        b //= 2
    return max(b, 1)


def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, *, bits: int, group: int,
                n_groups: int):
    # x_ref: [bt, D]; q_ref: [D, be] int8 (int4: [D/2, be] packed);
    # s_ref: [n_g, be] f32; o_ref: [bt, be]
    bt = x_ref.shape[0]
    be = o_ref.shape[1]
    half = group // 2

    def body(g, acc):
        xg = x_ref[:, pl.ds(pl.multiple_of(g * group, group), group)] \
            .astype(jnp.float32)
        sc = s_ref[pl.ds(g, 1), :]                            # [1, be]
        if bits == 8:
            wq = q_ref[pl.ds(pl.multiple_of(g * group, group), group), :]
            part = xg @ wq.astype(jnp.float32)
        else:
            # widen before shifting: the TPU vector unit has no int8 shifts
            p = q_ref[pl.ds(pl.multiple_of(g * half, half), half), :] \
                .astype(jnp.int32)
            lo = ((p << 28) >> 28).astype(jnp.float32)
            hi = (p >> 4).astype(jnp.float32)
            part = xg[:, :half] @ lo + xg[:, half:] @ hi
        return acc + part * sc

    acc = jax.lax.fori_loop(0, n_groups,
                            body, jnp.zeros((bt, be), jnp.float32))
    o_ref[...] = acc.astype(o_ref.dtype)


def quant_matmul(x, q, scales, *, block_t: int = 128, block_e: int = 128,
                 interpret: bool = False):
    """x [T, D] @ dequant(q, scales) -> [T, E] in x.dtype.

    ``q``: int8 codes [D, E] (int8) or nibble-packed [D//2, E] (int4, as
    produced by :func:`quantize_blockwise`); ``scales``: [D//g, E] f32.
    Dequantization happens in-register per group slab.
    """
    t, d = x.shape
    n_g, e = scales.shape
    bits = infer_bits(d, q)
    assert d % n_g == 0, (d, n_g)
    group = d // n_g
    bt = _fit_block(t, block_t)
    be = _fit_block(e, block_e)
    rows = q.shape[0]

    kernel = functools.partial(_qmm_kernel, bits=bits, group=group,
                               n_groups=n_g)
    return pl.pallas_call(
        kernel,
        grid=(t // bt, e // be),
        in_specs=[
            pl.BlockSpec((bt, d), lambda ti, ei: (ti, 0)),
            pl.BlockSpec((rows, be), lambda ti, ei: (0, ei)),
            pl.BlockSpec((n_g, be), lambda ti, ei: (0, ei)),
        ],
        out_specs=pl.BlockSpec((bt, be), lambda ti, ei: (ti, ei)),
        out_shape=jax.ShapeDtypeStruct((t, e), x.dtype),
        interpret=interpret,
    )(x, q, scales)
