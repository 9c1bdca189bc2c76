"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.paged_decode_attention import slot_shape


def flash_attention_ref(q, k, v, *, causal=True, window=0, softcap=0.0):
    b, sq, h, hd = q.shape
    _, sk, kh, _ = k.shape
    rep = h // kh
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qpos = jnp.arange(sq)[:, None] + (sk - sq)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > (qpos - window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def block_diag_matmul_ref(x, w):
    return jnp.einsum("btd,bde->bte", x.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)


def block_diag_dense_ref(x, w):
    """The dense equivalent: embed w into a big block-diagonal matrix."""
    bb, t, d = x.shape
    _, _, e = w.shape
    big = jnp.zeros((bb * d, bb * e), jnp.float32)
    for i in range(bb):
        big = big.at[i * d:(i + 1) * d, i * e:(i + 1) * e].set(
            w[i].astype(jnp.float32))
    xf = x.transpose(1, 0, 2).reshape(t, bb * d).astype(jnp.float32)
    out = xf @ big
    return out.reshape(t, bb, e).transpose(1, 0, 2).astype(x.dtype)


def moe_gmm_ref(x, w):
    return jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(x.dtype)


def ssm_scan_ref(a, b):
    """h_t = a_t h_{t-1} + b_t via lax.scan (time axis=1)."""
    def step(h, ab):
        at, bt = ab
        h = at.astype(jnp.float32) * h + bt.astype(jnp.float32)
        return h, h
    aT = jnp.swapaxes(a, 0, 1)
    bT = jnp.swapaxes(b, 0, 1)
    h0 = jnp.zeros(a.shape[:1] + a.shape[2:], jnp.float32)
    _, hs = jax.lax.scan(step, h0, (aT, bT))
    return jnp.swapaxes(hs, 0, 1).astype(a.dtype)


def decode_attention_ref(q, k_cache, v_cache, length, *, softcap=0.0):
    b, h, hd = q.shape
    _, L, kh, _ = k_cache.shape
    rep = h // kh
    k = jnp.repeat(k_cache, rep, axis=2)
    v = jnp.repeat(v_cache, rep, axis=2)
    s = jnp.einsum("bhd,blhd->bhl", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    valid = jnp.arange(L)[None, None, :] < length[:, None, None]
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhl,blhd->bhd", p, v.astype(jnp.float32)).astype(q.dtype)


def dequant_pool_ref(pool, scale):
    """Dequantize an int8 KV pool [P, bs, K, hd] with per-token-slot scales
    [P, bs, K] (one symmetric scale per token per kv head).  Identity for
    ``scale=None`` (f32 pools)."""
    if scale is None:
        return pool
    return pool.astype(jnp.float32) * scale[..., None]


def paged_decode_attention_ref(q, new, pool, layer, block_tables, lengths,
                               wb, wo, *, softcap=0.0):
    """Scatter-then-gather oracle for the paged decode kernel, on the same
    contract: the stacked pool leaves ``pool`` ("k"/"v" [N, P, bs,
    *slot_shape(K, hd, dtype)], the kernel's view; int8 pools with
    "k_scale"/"v_scale" [N, P, bs, K]) take the step's rows ``new`` (K/V
    [B, K, hd], scales [B, K]) at ``[layer, wb, wo]``, then q [B, H, hd]
    attends layer ``layer``'s pages through block_tables [B, NB] (entry 0 =
    reserved null block) over lengths [B] valid tokens, the new one
    included: each lane's blocks gather into a dense cache and
    ``decode_attention_ref`` decides.  Tables of different lanes may alias
    the same pages (prefix sharing).  A lane of length 0 returns zeros.
    Returns (out [B, H, hd], pool)."""
    b, kh, hd = new["k"].shape
    assert pool["k"].shape[3:] == slot_shape(kh, hd, pool["k"].dtype)
    pool = {key: leaf.at[layer, wb, wo].set(
                new[key].astype(leaf.dtype).reshape((b,) + leaf.shape[3:]))
            for key, leaf in pool.items()}
    kv = {key: pool[key][layer] for key in ("k", "v")}
    kv = {key: x.reshape(x.shape[:2] + (kh, hd)) for key, x in kv.items()}
    scale = {key: pool[key][layer] if key in pool else None
             for key in ("k_scale", "v_scale")}
    k = dequant_pool_ref(kv["k"], scale["k_scale"])
    v = dequant_pool_ref(kv["v"], scale["v_scale"])
    k, v = k[block_tables], v[block_tables]           # [B, NB, bs, K, hd]
    n = block_tables.shape[1] * k.shape[2]
    out = decode_attention_ref(q, k.reshape(b, n, kh, hd),
                               v.reshape(b, n, kh, hd), lengths,
                               softcap=softcap)
    return jnp.where(lengths[:, None, None] > 0, out, 0).astype(q.dtype), pool


def paged_prefill_attention_ref(q, k_pool, v_pool, block_tables, positions, *,
                                k_scale=None, v_scale=None, softcap=0.0):
    """Chunked-prefill attention against the paged pool (XLA path).

    q: [B, C, H, hd] — one chunk of C query tokens per lane at absolute
    positions ``positions`` [B, C]; k/v_pool: [P, bs, K, hd] pools that
    ALREADY contain this chunk's K/V (the caller scatters before attending);
    block_tables: [B, NB].  The gathered dense cache is in absolute position
    order (logical block j covers positions [j*bs, (j+1)*bs)), so the causal
    rule is just ``kpos <= qpos`` — it spans the cached prefix AND the
    in-chunk causal triangle in one mask.  Returns [B, C, H, hd]; rows of
    padded query slots are garbage (their writes routed to the null block
    and their outputs are never read).  ``k_scale``/``v_scale`` [P, bs, K]
    dequantize int8 pools first.
    """
    kd = dequant_pool_ref(k_pool, k_scale)[block_tables]  # [B, NB, bs, K, hd]
    vd = dequant_pool_ref(v_pool, v_scale)[block_tables]
    b, nb, bs, kh, hd = kd.shape
    kd = kd.reshape(b, nb * bs, kh, hd)
    vd = vd.reshape(b, nb * bs, kh, hd)
    h = q.shape[2]
    rep = h // kh
    kd = jnp.repeat(kd, rep, axis=2)
    vd = jnp.repeat(vd, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kd.astype(jnp.float32)) / math.sqrt(hd)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    kpos = jnp.arange(nb * bs)[None, None, None, :]
    mask = kpos <= positions[:, None, :, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      vd.astype(jnp.float32)).astype(q.dtype)


def quant_matmul_ref(x, q, scales, *, bits=None):
    """Dequantize-then-matmul oracle for the blockwise quant GEMM kernel."""
    from repro.kernels.quant_matmul import dequantize_blockwise, infer_bits
    if bits is None:
        bits = infer_bits(x.shape[-1], q)
    w = dequantize_blockwise(q, scales, bits=bits)
    return (x.astype(jnp.float32) @ w).astype(x.dtype)
