"""Grouped matmul over expert segments (MoE) — Pallas TPU kernel.

After sort-based dispatch, tokens sit in an [E, C, d] buffer (C = capacity).
Each expert applies its own [d, f] weight.  Grid: (E, C/BLOCK_C, f/BLOCK_F);
the contraction is streamed in BLOCK_D slabs through VMEM.  On TPU this is
the standard "dense GMM" form (capacity padding keeps shapes static for the
MXU; the Megablocks-style ragged form does not map to the systolic array
without padding anyway).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gmm_kernel(x_ref, w_ref, o_ref, *, block_d: int, d: int):
    @functools.partial(jax.lax.fori_loop, 0, d // block_d,
                       init_val=jnp.zeros(o_ref.shape, jnp.float32))
    def acc(i, acc):
        off = pl.multiple_of(i * block_d, block_d)
        xs = x_ref[:, pl.ds(off, block_d)]
        ws = w_ref[pl.ds(off, block_d), :]
        return acc + xs.astype(jnp.float32) @ ws.astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def moe_gmm(x, w, *, block_c: int = 128, block_f: int = 128,
            block_d: int = 128, interpret: bool = False):
    """x: [E, C, d]; w: [E, d, f] -> [E, C, f]."""
    e, c, d = x.shape
    _, _, f = w.shape
    block_c = min(block_c, c)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    assert c % block_c == 0 and f % block_f == 0 and d % block_d == 0

    kernel = functools.partial(_gmm_kernel, block_d=block_d, d=d)
    return pl.pallas_call(
        kernel,
        grid=(e, c // block_c, f // block_f),
        in_specs=[
            pl.BlockSpec((None, block_c, d), lambda ei, ci, fi: (ei, ci, 0)),
            pl.BlockSpec((None, d, block_f), lambda ei, ci, fi: (ei, 0, fi)),
        ],
        out_specs=pl.BlockSpec((None, block_c, block_f),
                               lambda ei, ci, fi: (ei, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        interpret=interpret,
    )(x, w)
