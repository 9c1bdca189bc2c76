"""Single-token decode attention against a long KV cache — Pallas TPU kernel.

Decode is memory-bound: the whole KV cache streams HBM->VMEM once per token.
The kernel tiles the cache length into BLOCK_L slabs, keeps the running
(max, sum, acc) flash state in VMEM, and masks invalid slots (cache fill
level / ring-buffer windows) via the `length` operand.

Grid: (B, K_heads); queries for the GQA group (H/K heads) ride together so
the cache is read ONCE per kv head, not per q head.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, block_l: int,
                   L: int, scale: float, softcap: float):
    # len_ref: [B] (SMEM, scalar prefetch); q_ref: [rep, hd];
    # k_ref/v_ref: [L, hd]; o_ref: [rep, hd]
    rep, hd = q_ref.shape
    q = q_ref[...].astype(jnp.float32) * scale
    valid_len = len_ref[pl.program_id(0)]

    def body(i, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.ds(i * block_l, block_l), :]
        v = v_ref[pl.ds(i * block_l, block_l), :]
        s = q @ k.astype(jnp.float32).T                     # [rep, bl]
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        pos = i * block_l + jax.lax.iota(jnp.int32, block_l)
        s = jnp.where(pos[None, :] < valid_len, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_prev + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v.astype(jnp.float32)
        return acc, m_cur, l_cur

    n_l = pl.cdiv(L, block_l)
    # skip blocks entirely beyond the fill level
    n_eff = jnp.minimum(n_l, pl.cdiv(valid_len, block_l)).astype(jnp.int32)
    acc0 = jnp.zeros((rep, hd), jnp.float32)
    m0 = jnp.full((rep,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rep,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_eff, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, length, *, block_l: int = 256,
                     softcap: float = 0.0, interpret: bool = False):
    """q: [B, H, hd] (one token); k/v_cache: [B, L, K, hd]; length: [B] valid
    slots.  Returns [B, H, hd]."""
    b, h, hd = q.shape
    _, L, kh, _ = k_cache.shape
    assert h % kh == 0
    rep = h // kh
    block_l = min(block_l, L)
    assert L % block_l == 0
    scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(b, kh, rep, hd)
    kt = k_cache.transpose(0, 2, 1, 3)         # [B, K, L, hd]
    vt = v_cache.transpose(0, 2, 1, 3)

    kernel = functools.partial(_decode_kernel, block_l=block_l, L=L,
                               scale=scale, softcap=softcap)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kh),
            in_specs=[
                pl.BlockSpec((None, None, rep, hd),
                             lambda bi, ki, _: (bi, ki, 0, 0)),
                pl.BlockSpec((None, None, L, hd),
                             lambda bi, ki, _: (bi, ki, 0, 0)),
                pl.BlockSpec((None, None, L, hd),
                             lambda bi, ki, _: (bi, ki, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, rep, hd),
                                   lambda bi, ki, _: (bi, ki, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, rep, hd), q.dtype),
        interpret=interpret,
    )(length.astype(jnp.int32), qg, kt, vt)
    return out.reshape(b, h, hd)
