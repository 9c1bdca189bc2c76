"""Paged single-token decode attention — Pallas TPU kernel.

The continuous-batching decode path (``repro.decode``) keeps the KV cache as
a pool of fixed-size physical blocks; a per-sequence block table maps logical
block j to its physical slot.  This kernel walks the block table, DMA-gathers
one physical K/V block per step, and folds it into the running flash
(max, sum, acc) state — the same online-softmax pattern as
``decode_attention``, but the cache never has to be contiguous per sequence.

Grid: (B, K_heads); the GQA group's queries (H/K heads) ride together so each
physical block is read ONCE per kv head.  Blocks past the sequence's fill
level are skipped entirely; partial tail blocks are masked via ``lengths``.
Block id 0 is the allocator's reserved null block: padded table entries point
there and are never attended (they sit beyond the fill level).

With prefix sharing (PR 4) block tables of different lanes may ALIAS the
same physical block (a shared prompt head).  The kernel only ever gathers
through the table — the pool refs are read-only — so aliasing needs no
special handling; tests cover aliased tables against the dense reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(len_ref, bt_ref, q_ref, k_ref, v_ref, *rest,
                  block_size: int, scale: float, softcap: float,
                  quantized: bool):
    # scalar prefetch (SMEM): len_ref [B], bt_ref [B, NB].  q_ref: [rep, hd];
    # k_ref/v_ref: [P, bs, hd] (this kv head's pool); with quantized=True
    # two [P, 1, bs] per-slot scale refs precede o_ref.  o_ref: [rep, hd]
    if quantized:
        ks_ref, vs_ref, o_ref = rest
    else:
        (o_ref,) = rest
    rep, hd = q_ref.shape
    bi = pl.program_id(0)
    nb = bt_ref.shape[1]
    q = q_ref[...].astype(jnp.float32) * scale
    valid_len = len_ref[bi]

    def body(j, carry):
        acc, m_prev, l_prev = carry
        bid = bt_ref[bi, j]                               # physical block id
        k = k_ref[bid].astype(jnp.float32)                # [bs, hd]
        v = v_ref[bid].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if quantized:
            # dequant epilogue: one f32 scale per (token slot, kv head), so
            # q.(k*ks) == (q.k)*ks and p@(v*vs) == (p*vs)@v — the scales
            # apply to the [rep, bs] scores instead of the [bs, hd] codes
            s = s * ks_ref[bid]
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        s = jnp.where(pos < valid_len, s, NEG_INF)          # [rep, bs]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = p * vs_ref[bid] if quantized else p
        acc = acc * alpha + jnp.dot(pv, v, preferred_element_type=jnp.float32)
        return acc, m_cur, l_cur

    # walk only the logical blocks below the fill level
    n_eff = jnp.minimum(nb, pl.cdiv(valid_len, block_size))
    acc0 = jnp.zeros((rep, hd), jnp.float32)
    m0 = jnp.full((rep, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rep, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_eff, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths, *,
                           k_scale=None, v_scale=None, softcap: float = 0.0,
                           interpret: bool = False):
    """q: [B, H, hd] (one token per sequence); k/v_pool: [P, bs, K, hd]
    physical block pools; block_tables: [B, NB] int32; lengths: [B] valid
    token counts.  Optional ``k_scale``/``v_scale`` [P, bs, K] dequantize
    int8 pools in-register.  Returns [B, H, hd].

    Lengths and block tables ride in SMEM (scalar prefetch) and are indexed
    by the program's batch row; each kv head's whole pool is one VMEM block,
    indexed by physical block id along its leading axis.  That axis carries
    no tiling constraint, so any ``block_size`` and pool dtype is legal —
    but the pool per kv head must fit VMEM."""
    b, h, hd = q.shape
    p_blocks, bs, kh, _ = k_pool.shape
    nb = block_tables.shape[1]
    assert h % kh == 0
    rep = h // kh
    scale = 1.0 / math.sqrt(hd)
    quantized = k_scale is not None
    assert (v_scale is not None) == quantized

    qg = q.reshape(b, kh, rep, hd)
    # pool per kv head: [K, P, bs, hd], physical block j at index j
    kt = k_pool.transpose(2, 0, 1, 3)
    vt = v_pool.transpose(2, 0, 1, 3)

    pool_spec = pl.BlockSpec((None, p_blocks, bs, hd),
                             lambda bi, ki, *_: (ki, 0, 0, 0))
    in_specs = [
        pl.BlockSpec((None, None, rep, hd), lambda bi, ki, *_: (bi, ki, 0, 0)),
        pool_spec, pool_spec,
    ]
    args = [qg, kt, vt]
    if quantized:
        # per-slot scales as lane rows [K, P, 1, bs]
        scale_spec = pl.BlockSpec((None, p_blocks, 1, bs),
                                  lambda bi, ki, *_: (ki, 0, 0, 0))
        in_specs += [scale_spec, scale_spec]
        args += [s.transpose(2, 0, 1).reshape(kh, p_blocks, 1, bs)
                 .astype(jnp.float32) for s in (k_scale, v_scale)]

    kernel = functools.partial(_paged_kernel, block_size=bs, scale=scale,
                               softcap=softcap, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kh),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, None, rep, hd),
                                   lambda bi, ki, *_: (bi, ki, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, rep, hd), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32), *args)
    return out.reshape(b, h, hd)
