"""Blocked (flash) causal GQA attention — Pallas TPU kernel.

TPU adaptation of the standard flash algorithm: the [Sq] axis is tiled into
VMEM blocks of BLOCK_Q rows, the [Sk] axis is streamed in BLOCK_K columns;
running (max, sum, acc) live in VREGs/VMEM scratch.  Block shapes are multiples
of 128 to keep the MXU systolic array full.

Grid: (batch, q_heads, Sq / BLOCK_Q); each program accumulates over the
Sk / BLOCK_K inner loop with lax.fori_loop.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, sk: int,
                 causal: bool, window: int, softcap: float, scale: float):
    # q_ref: [block_q, hd]; k_ref/v_ref: [sk, hd]; o_ref: [block_q, hd]
    block_q, hd = q_ref.shape
    q = q_ref[...].astype(jnp.float32) * scale
    q_idx = pl.program_id(2)
    n_q = pl.num_programs(2)
    # queries sit at the END of the key range (prefill continuation)
    q_off = sk - n_q * block_q
    q_pos = q_off + q_idx * block_q + jax.lax.iota(jnp.int32, block_q)

    def body(i, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.ds(i * block_k, block_k), :]
        v = v_ref[pl.ds(i * block_k, block_k), :]
        s = q @ k.astype(jnp.float32).T                     # [bq, bk]
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        k_pos = i * block_k + jax.lax.iota(jnp.int32, block_k)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= k_pos[None, :] > (q_pos[:, None] - window)
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_prev + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v.astype(jnp.float32)
        return acc, m_cur, l_cur

    n_k = sk // block_k
    if causal:
        # skip fully-masked key blocks beyond the last query row
        n_k_eff = jnp.minimum(
            n_k, (q_off + (q_idx + 1) * block_q) // block_k + 1).astype(jnp.int32)
    else:
        n_k_eff = n_k
    acc0 = jnp.zeros((block_q, hd), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_k_eff, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K, interpret: bool = False):
    """q: [B, Sq, H, hd]; k, v: [B, Sk, K, hd] (GQA: H % K == 0).
    Returns [B, Sq, H, hd]."""
    b, sq, h, hd = q.shape
    _, sk, kh, _ = k.shape
    assert h % kh == 0
    rep = h // kh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    scale = 1.0 / math.sqrt(hd)

    # layout: [B, H, Sq, hd] program per (b, h, q_block)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _attn_kernel, block_k=block_k, sk=sk, causal=causal, window=window,
        softcap=softcap, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, sq // block_q),
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, sk, hd),
                         lambda bi, hi, qi, rep=rep: (bi, hi // rep, 0, 0)),
            pl.BlockSpec((None, None, sk, hd),
                         lambda bi, hi, qi, rep=rep: (bi, hi // rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
