"""Block-diagonal (semantic-split) matmul — Pallas TPU kernel.

THE paper-technique kernel: a semantic split turns every weight matrix into B
independent diagonal blocks (SplitNet).  Computing it as one dense matmul
wastes B^2/B of the MACs; this kernel computes branch b's [T, d_b] x
[d_b, e_b] product only.

Grid: (branch, T / BLOCK_T, e_b / BLOCK_E); the contraction dim d_b is
streamed through VMEM in BLOCK_D slabs.  All block dims are 128-aligned for
the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bdm_kernel(x_ref, w_ref, o_ref, *, block_d: int, d_b: int):
    # x_ref: [block_t, d_b]; w_ref: [d_b, block_e]; o_ref: [block_t, block_e]
    @functools.partial(jax.lax.fori_loop, 0, d_b // block_d,
                       init_val=jnp.zeros(o_ref.shape, jnp.float32))
    def acc(i, acc):
        off = pl.multiple_of(i * block_d, block_d)
        xs = x_ref[:, pl.ds(off, block_d)]
        ws = w_ref[pl.ds(off, block_d), :]
        return acc + xs.astype(jnp.float32) @ ws.astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def block_diag_matmul(x, w, *, block_t: int = 128, block_e: int = 128,
                      block_d: int = 128, interpret: bool = False):
    """x: [Bb, T, d_b]; w: [Bb, d_b, e_b] -> [Bb, T, e_b].

    Equivalent to a dense [T, Bb*d_b] x [Bb*d_b, Bb*e_b] matmul against the
    block-diagonal embedding of w, at 1/Bb of the FLOPs.
    """
    bb, t, d_b = x.shape
    _, _, e_b = w.shape
    block_t = min(block_t, t)
    block_e = min(block_e, e_b)
    block_d = min(block_d, d_b)
    assert t % block_t == 0 and e_b % block_e == 0 and d_b % block_d == 0

    kernel = functools.partial(_bdm_kernel, block_d=block_d, d_b=d_b)
    return pl.pallas_call(
        kernel,
        grid=(bb, t // block_t, e_b // block_e),
        in_specs=[
            pl.BlockSpec((None, block_t, d_b), lambda bi, ti, ei: (bi, ti, 0)),
            pl.BlockSpec((None, d_b, block_e), lambda bi, ti, ei: (bi, 0, ei)),
        ],
        out_specs=pl.BlockSpec((None, block_t, block_e),
                               lambda bi, ti, ei: (bi, ti, ei)),
        out_shape=jax.ShapeDtypeStruct((bb, t, e_b), x.dtype),
        interpret=interpret,
    )(x, w)
