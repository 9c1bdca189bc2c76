"""Paged chunked-prefill attention — Pallas TPU kernel.

The chunked-prefill hot path commits C prompt tokens per lane into the paged
pool and then attends each chunk token over its cached prefix AND the
in-chunk causal triangle.  The XLA reference does this with a dense
``k_pool[block_tables]`` gather — materializing [B, NB*bs, K, hd] per layer.
This kernel walks the block table instead (same pattern as
``paged_decode_attention``): one physical block per step folded into the
running flash (max, sum, acc) state, the GQA group's queries riding
together, and the absolute-position causal rule ``kpos <= qpos`` masking
the cached prefix and the in-chunk triangle in one comparison (the caller
scatters the chunk's K/V before attending, so a query's own token is always
a valid key — no empty softmax rows).

A dequant epilogue handles int8 KV blocks: when per-token-slot scales are
passed, gathered code blocks are widened and scaled in-register, so the
same kernel serves f32 and quantized pools.

Padded query slots (lanes past their valid ``n_tok``) have their writes
routed to the null block by the caller; their output rows are garbage by
design and never read.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _chunk_kernel(last_ref, bt_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                  block_size: int, scale: float, softcap: float,
                  quantized: bool):
    # scalar prefetch (SMEM): last_ref [B] (each lane's last chunk
    # position), bt_ref [B, NB].  pos_ref: [R, 1] and q_ref: [R, hd] with
    # R = C * rep query rows (token-major within the GQA group);
    # k_ref/v_ref: [P, bs, hd] (this kv head's pool); with quantized=True
    # two [P, 1, bs] per-slot scale refs precede o_ref.  o_ref: [R, hd]
    if quantized:
        ks_ref, vs_ref, o_ref = rest
    else:
        (o_ref,) = rest
    r, hd = q_ref.shape
    bi = pl.program_id(0)
    nb = bt_ref.shape[1]
    q = q_ref[...].astype(jnp.float32) * scale
    qpos = pos_ref[...]                                      # [R, 1]

    def body(j, carry):
        acc, m_prev, l_prev = carry
        bid = bt_ref[bi, j]                                  # physical block
        k = k_ref[bid].astype(jnp.float32)                   # [bs, hd]
        v = v_ref[bid].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if quantized:
            # per-slot scales fold into the scores and probabilities (see
            # paged_decode_attention)
            s = s * ks_ref[bid]
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_size), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)             # [R, bs]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pv = p * vs_ref[bid] if quantized else p
        acc = acc * alpha + jnp.dot(pv, v, preferred_element_type=jnp.float32)
        return acc, m_cur, l_cur

    # walk only the logical blocks at or below the chunk's last position
    n_eff = jnp.minimum(nb, pl.cdiv(last_ref[bi] + 1, block_size))
    acc0 = jnp.zeros((r, hd), jnp.float32)
    m0 = jnp.full((r, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((r, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_eff, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def paged_prefill_attention(q, k_pool, v_pool, block_tables, positions, *,
                            k_scale=None, v_scale=None, softcap: float = 0.0,
                            interpret: bool = False):
    """q: [B, C, H, hd] (one chunk per lane at absolute ``positions``
    [B, C]); k/v_pool: [P, bs, K, hd] pools that already contain this
    chunk's K/V; block_tables: [B, NB].  Optional ``k_scale``/``v_scale``
    [P, bs, K] dequantize int8 pools in-register.  Returns [B, C, H, hd].

    Block tables and each lane's last position ride in SMEM (scalar
    prefetch), indexed by the program's batch row; the pool layout is the
    one ``paged_decode_attention`` uses."""
    b, c, h, hd = q.shape
    p_blocks, bs, kh, _ = k_pool.shape
    nb = block_tables.shape[1]
    assert h % kh == 0
    rep = h // kh
    r = c * rep
    scale = 1.0 / math.sqrt(hd)
    quantized = k_scale is not None
    assert (v_scale is not None) == quantized

    positions = positions.astype(jnp.int32)
    # queries grouped by kv head (h = kv_head * rep + g, kv head major), the
    # group's rows token-major: row t * rep + g holds token t, head g
    qg = q.reshape(b, c, kh, rep, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, kh, r, hd)
    qpos = jnp.repeat(positions, rep, axis=1)[:, :, None]     # [B, R, 1]
    kt = k_pool.transpose(2, 0, 1, 3)
    vt = v_pool.transpose(2, 0, 1, 3)

    pool_spec = pl.BlockSpec((None, p_blocks, bs, hd),
                             lambda bi, ki, *_: (ki, 0, 0, 0))
    in_specs = [
        pl.BlockSpec((None, r, 1), lambda bi, ki, *_: (bi, 0, 0)),
        pl.BlockSpec((None, None, r, hd), lambda bi, ki, *_: (bi, ki, 0, 0)),
        pool_spec, pool_spec,
    ]
    args = [qpos, qg, kt, vt]
    if quantized:
        scale_spec = pl.BlockSpec((None, p_blocks, 1, bs),
                                  lambda bi, ki, *_: (ki, 0, 0, 0))
        in_specs += [scale_spec, scale_spec]
        args += [s.transpose(2, 0, 1).reshape(kh, p_blocks, 1, bs)
                 .astype(jnp.float32) for s in (k_scale, v_scale)]

    kernel = functools.partial(_chunk_kernel, block_size=bs, scale=scale,
                               softcap=softcap, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kh),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, None, r, hd),
                                   lambda bi, ki, *_: (bi, ki, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, r, hd), q.dtype),
        interpret=interpret,
    )(jnp.max(positions, axis=1), block_tables.astype(jnp.int32), *args)
    return out.reshape(b, kh, c, rep, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, c, h, hd)
