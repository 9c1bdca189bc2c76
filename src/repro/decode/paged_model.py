"""Paged decode forward passes: chunked prefill + the fused scan decode loop.

Three jit-friendly builders over a ``repro.models`` model (single-branch
``Model`` or the paper's ``SemanticModel``):

``make_prefill_chunk_fn``  one jitted call commits up to ``chunk`` prompt
                    tokens per prefilling lane *directly into the paged
                    pool*: per layer the chunk's K/V scatter to their block
                    slots, then the queries attend through the block table —
                    over the cached prefix (prefix-sharing hits included)
                    and the in-chunk causal triangle in one mask.  Long
                    uncached tails commit chunk by chunk, interleaved with
                    decode dispatches, instead of one monolithic prefill
                    (this replaced PR 3's dense ``prefill_cache`` + block
                    scatter join path).
``make_decode_fn``  the fused decode loop: ``lax.scan`` over K tokens, so
                    decode costs ONE jitted dispatch per K tokens instead of
                    one per token.  Per-lane ``remaining`` masks retire lanes
                    mid-scan (writes route to the null block, lengths
                    freeze), so a dispatch never overruns a lane's block
                    allocation.
``paged_decode_logits``  a single paged decode step (used by the scan body
                    and directly by parity tests).

Both paged attention paths dispatch to their Pallas kernels
(``paged_decode_attention``, ``paged_prefill_attention``) on TPU backends
and to the dense-gather XLA references elsewhere — the same dispatch
convention as ``repro.models.attention``.  The decode step hands its kernel
the whole stacked pool and the layer index, and the kernel writes the new
token and reads each lane's pages where they lie; the prefill chunk scans
per-layer pool entries.  Block tables may alias physical blocks across
lanes (prefix sharing); the attention paths only ever gather through the
table, and a lane writes only its own blocks, so aliasing is read-only.

Quantized serving rides the same forwards: an int8 pool (``"k_scale"`` /
``"v_scale"`` leaves — see ``paged_cache.quantize_pool``) makes every
scatter quantize-on-write (per-token symmetric scales, so block content is
a pure function of the token's K/V and prefix hits replay bit-exactly) and
every attend dequantize-in-register; dict-valued projection weights
(``{"q", "scale"}`` from :func:`quantize_attn_params`) route the four
attention matmuls through the blockwise int8/int4 dequant GEMM kernel.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.decode.paged_cache import (is_scale_path, chunk_write_slots,
                                      quantize_kv, write_slots)
from repro.kernels import ref
from repro.kernels.paged_decode_attention import (paged_decode_attention,
                                                  slot_shape)
from repro.kernels.paged_prefill_attention import paged_prefill_attention
from repro.kernels.quant_matmul import (dequantize_blockwise, infer_bits,
                                        quant_matmul, quantize_blockwise)
from repro.models import layers as L
from repro.models import moe as M
from repro.models.model import Model, SemanticModel

#: the serving-side projection weights eligible for blockwise quantization
ATTN_PROJ = ("wq", "wk", "wv", "wo")


def supports_paged_decode(model) -> bool:
    """Paged decode needs pure global-attention mixers (same gate as
    single-step prefill): recurrent state and ring buffers are not paged."""
    return getattr(model, "supports_single_step_prefill", False)


def quantize_attn_params(params, bits: int):
    """Serving-side blockwise weight quantization of the attention
    projections (wq/wk/wv/wo) in every block of ``params``.

    Returns ``(new_params, telemetry)``: a NEW params tree (the caller's
    f32 params are untouched — train/legacy paths keep using them) where
    each projection leaf becomes a ``{"q", "scale"}`` dict consumed by
    :func:`_proj`, plus max/mean absolute dequantization error over all
    quantized weights.  Norms, embeddings and FFN weights stay f32 (the
    projections are the per-token serving matmuls the paged path owns).
    """
    errs_max, errs_sum, errs_n = [], [], 0
    def q_one(w):
        nonlocal errs_n
        q, s = quantize_blockwise(w, bits=bits)
        deq = dequantize_blockwise(q, s, bits=bits)
        err = jnp.abs(deq - w.astype(jnp.float32))
        errs_max.append(jnp.max(err))
        errs_sum.append(jnp.sum(err))
        errs_n += err.size
        return {"q": q, "scale": s}

    new_blocks = {}
    for pos, blk in params["blocks"].items():
        nb = dict(blk)
        mix = dict(blk["mix"])
        for name in ATTN_PROJ:
            mix[name] = q_one(mix[name])
        nb["mix"] = mix
        new_blocks[pos] = nb
    new_params = dict(params)
    new_params["blocks"] = new_blocks
    tele = {
        "weight_quant_bits": bits,
        "weight_quant_max_err": round(float(jnp.max(jnp.stack(errs_max))), 6),
        "weight_quant_mean_err": round(
            float(jnp.sum(jnp.stack(errs_sum))) / max(errs_n, 1), 6),
    }
    return new_params, tele


def _proj(x, w, interpret: bool):
    """x [B, S, D] @ w — w is either a plain f32 matrix or a quantized
    ``{"q", "scale"}`` dict, routed through the blockwise dequant GEMM
    (Pallas kernel on TPU/interpret, jnp dequant reference elsewhere)."""
    if not isinstance(w, dict):
        return x @ w
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    if interpret or jax.default_backend() == "tpu":
        out = quant_matmul(xf, w["q"], w["scale"], interpret=interpret)
    else:
        out = ref.quant_matmul_ref(xf, w["q"], w["scale"],
                                   bits=infer_bits(d, w["q"]))
    return out.reshape(b, s, -1)


def _attend(q, new, pool, layer, block_tables, valid_lens, wb, wo, softcap,
            interpret: bool):
    args = (q, new, pool, layer, block_tables, valid_lens, wb, wo)
    if interpret or jax.default_backend() == "tpu":
        return paged_decode_attention(*args, softcap=softcap,
                                      interpret=interpret)
    return ref.paged_decode_attention_ref(*args, softcap=softcap)


def _chunk_attend(q, k_pool, v_pool, block_tables, positions, softcap,
                  interpret: bool, k_scale=None, v_scale=None):
    if interpret or jax.default_backend() == "tpu":
        return paged_prefill_attention(q, k_pool, v_pool, block_tables,
                                       positions, k_scale=k_scale,
                                       v_scale=v_scale, softcap=softcap,
                                       interpret=interpret)
    return ref.paged_prefill_attention_ref(q, k_pool, v_pool, block_tables,
                                           positions, k_scale=k_scale,
                                           v_scale=v_scale, softcap=softcap)


def pages_view(pool):
    """The pool as the decode kernel reads it: each K/V leaf
    ``[..., K, hd]`` reshaped to ``[..., *slot_shape(K, hd, dtype)]``
    (128-lane rows of heads side by side when hd < 128); scale leaves as
    they are.  The same values in the same order, so a reshape back undoes
    it."""
    def leaf(path, x):
        if is_scale_path(path):
            return x
        return x.reshape(x.shape[:-2] + slot_shape(*x.shape[-2:], x.dtype))
    return jax.tree_util.tree_map_with_path(leaf, pool)


def _kv_slots(pool, k, v):
    """The pool leaves' contents for new K/V, quantized on write when the
    pool carries int8 code + scale leaves.  Per-token scales mean each
    written slot depends only on its own K/V vector — chunk prefill, decode
    steps and COW copies all commit identical bytes for identical tokens."""
    if "k_scale" in pool:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}
    return {"k": k.astype(pool["k"].dtype), "v": v.astype(pool["v"].dtype)}


def _scatter_kv(pool, k, v, wb, wo):
    """Scatter new K/V into their (wb, wo) slots of one layer's pool."""
    return {key: pool[key].at[wb, wo].set(x)
            for key, x in _kv_slots(pool, k, v).items()}


def _paged_attn(params, x, cfg: ArchConfig, *, positions, pool, layer,
                block_tables, valid_lens, wb, wo, interpret: bool):
    """One-token GQA attention against layer ``layer`` of the stacked paged
    pool: the new K/V go to their (wb, wo) write slots and the query
    attends through the block table, in one kernel call that updates the
    pool in place."""
    b, s, _ = x.shape                       # s == 1
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = _proj(x, params["wq"], interpret).reshape(b, s, h, hd)
    k = _proj(x, params["wk"], interpret).reshape(b, s, kv, hd)
    v = _proj(x, params["wv"], interpret).reshape(b, s, kv, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    out, npool = _attend(q[:, 0], _kv_slots(pool, k[:, 0], v[:, 0]), pool,
                         layer, block_tables, valid_lens, wb, wo,
                         cfg.attn_softcap, interpret)
    out = _proj(out.reshape(b, s, h * hd), params["wo"], interpret)
    return out, npool


def _paged_chunk_attn(params, x, cfg: ArchConfig, *, positions, pool,
                      block_tables, wb, wo, interpret: bool):
    """Chunk GQA attention against the paged pool: scatter the chunk's K/V
    into their (wb, wo) slots, then attend through the block table with the
    absolute-position causal mask (cached prefix + in-chunk triangle)."""
    b, s, _ = x.shape                       # s == chunk
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = _proj(x, params["wq"], interpret).reshape(b, s, h, hd)
    k = _proj(x, params["wk"], interpret).reshape(b, s, kv, hd)
    v = _proj(x, params["wv"], interpret).reshape(b, s, kv, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    npool = _scatter_kv(pool, k, v, wb, wo)
    out = _chunk_attend(q, npool["k"], npool["v"], block_tables, positions,
                        cfg.attn_softcap, interpret,
                        k_scale=npool.get("k_scale"),
                        v_scale=npool.get("v_scale"))
    out = _proj(out.reshape(b, s, h * hd), params["wo"], interpret)
    return out, npool


def _stack_body(cfg: ArchConfig, h, sb_params, sb_pool, attn_fn):
    """One superblock of the paged forward; ``attn_fn(blk_params, hn,
    sb_pool_entry)`` returns (mix_out, new_pool_entry)."""
    new_sb_pool = {}
    for i, (mixer, ffn) in enumerate(cfg.pattern):
        assert mixer == "attn", "paged decode requires global attention"
        blk = sb_params[f"pos{i}"]
        hn = L.norm_apply(blk["mix_norm"], h, cfg)
        out, npool = attn_fn(blk["mix"], hn, sb_pool[f"pos{i}"])
        if cfg.post_norms:
            out = L.norm_apply(blk["mix_post_norm"], out, cfg)
        h = h + out
        if ffn != "none":
            hn = L.norm_apply(blk["ffn_norm"], h, cfg)
            if ffn == "dense":
                out = L.mlp_apply(blk["ffn"], hn, cfg)
            else:
                out, _ = M.moe_apply(blk["ffn"], hn, cfg)
            if cfg.post_norms:
                out = L.norm_apply(blk["ffn_post_norm"], out, cfg)
            h = h + out
        new_sb_pool[f"pos{i}"] = npool
    return h, new_sb_pool


def _scan_stack(cfg: ArchConfig, params, pool, x, attn_fn):
    """Scan the superblock stack with the pool in the loop carry: each
    superblock reads its pool entry and writes it back
    (``dynamic_update_index_in_dim``; on the chip XLA copies the entry out
    and back around the attention, which the decode step's
    :func:`_decode_stack` avoids).  Passing the pool as scanned
    inputs/outputs instead makes XLA hold a second full pool for the
    stacked outputs, which at published widths does not fit one chip."""
    def body(carry, xs):
        h, pool = carry
        sb_params, i = xs
        sb_pool = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            pool)
        h, new_sb_pool = _stack_body(cfg, h, sb_params, sb_pool, attn_fn)
        pool = jax.tree.map(
            lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, i, 0),
            pool, new_sb_pool)
        return (h, pool), None

    n_sb = jax.tree.leaves(pool)[0].shape[0]
    (x, pool), _ = jax.lax.scan(body, (x, pool),
                                (params["blocks"], jnp.arange(n_sb)))
    return x, pool


def _decode_stack(cfg: ArchConfig, params, pool, x, attn_fn):
    """Scan the superblock stack for one decode token with the whole
    stacked pool in the loop carry: ``attn_fn(blk_params, hn, entry, i)``
    gets superblock i's stacked pool entry and the index, and its kernel
    reads and writes layer i where it lies, so no pool leaf is sliced out
    or written back per layer."""
    def body(carry, xs):
        h, pool = carry
        sb_params, i = xs
        h, pool = _stack_body(cfg, h, sb_params, pool,
                              lambda p, hn, entry: attn_fn(p, hn, entry, i))
        return (h, pool), None

    n_sb = jax.tree.leaves(pool)[0].shape[0]
    (x, pool), _ = jax.lax.scan(body, (x, pool),
                                (params["blocks"], jnp.arange(n_sb)))
    return x, pool


def _paged_step_one(model: Model, params, pool, tokens, block_tables,
                    lengths, active, *, interpret: bool):
    """Single-branch paged decode step.  tokens: [B, 1]; lengths: [B] tokens
    already in cache (== the new token's position).  Returns
    ([B, vocab] logits, new_pool)."""
    cfg = model.cfg
    # pool leaves are [N_sb, P, bs, K, hd]; block size from any leaf
    block_size = jax.tree.leaves(pool)[0].shape[2]
    x = L.embed_apply(params["embed"], tokens, cfg)
    positions = lengths[:, None]
    wb, wo = write_slots(lengths, block_tables, active, block_size)
    valid_lens = lengths + active.astype(jnp.int32)

    attn = lambda p, hn, entry, i: _paged_attn(
        p, hn, cfg, positions=positions, pool=entry, layer=i,
        block_tables=block_tables, valid_lens=valid_lens, wb=wb, wo=wo,
        interpret=interpret)
    x, new_pool = _decode_stack(cfg, params, pool, x, attn)
    x = L.norm_apply(params["final_norm"], x, cfg)
    logits = L.unembed_apply(params["embed"], x, cfg)
    return logits[:, -1], new_pool


def _paged_chunk_one(model: Model, params, pool, tokens, starts, n_tok,
                     block_tables, *, interpret: bool = False):
    """Single-branch chunked prefill: commit ``tokens`` [B, C] at absolute
    positions ``starts + [0..C)`` into the paged pool and return the logits
    at each lane's last valid chunk position.  Padded token slots (>= n_tok)
    write to the null block and their outputs are never read."""
    cfg = model.cfg
    block_size = jax.tree.leaves(pool)[0].shape[2]
    b, c = tokens.shape
    x = L.embed_apply(params["embed"], tokens, cfg)
    positions = starts[:, None] + jnp.arange(c)[None, :]
    wb, wo = chunk_write_slots(starts, n_tok, block_tables, block_size, c)

    attn = lambda p, hn, entry: _paged_chunk_attn(
        p, hn, cfg, positions=positions, pool=entry,
        block_tables=block_tables, wb=wb, wo=wo, interpret=interpret)
    x, new_pool = _scan_stack(cfg, params, pool, x, attn)
    x = L.norm_apply(params["final_norm"], x, cfg)
    idx = jnp.clip(n_tok - 1, 0, c - 1)[:, None, None]
    x = jnp.take_along_axis(x, jnp.broadcast_to(
        idx, (b, 1, x.shape[2])), axis=1)
    logits = L.unembed_apply(params["embed"], x, cfg)
    return logits[:, -1], new_pool


def paged_decode_logits(model, params, pool, tokens, block_tables, lengths,
                        active, *, interpret: bool = False):
    """One paged decode step for either model flavor.  Semantic models vmap
    the branch step over (params, pool) and merge the vocab shards.  The
    pool is in :func:`pages_view`, the kernel's view of its K/V leaves, and
    comes back in it."""
    if isinstance(model, SemanticModel):
        step = lambda p, c: _paged_step_one(
            model.branch, p, c, tokens, block_tables, lengths, active,
            interpret=interpret)
        logits, new_pool = jax.vmap(step)(params, pool)
        bb, b, v = logits.shape
        return jnp.transpose(logits, (1, 0, 2)).reshape(b, bb * v), new_pool
    return _paged_step_one(model, params, pool, tokens, block_tables,
                           lengths, active, interpret=interpret)


# ---------------------------------------------------------------- factories
def make_prefill_chunk_fn(model, *, interpret: bool = False):
    """(params, pool, toks [W, C], starts [W], n_tok [W], block_tables
    [W, NB]) -> ([W, vocab] last-valid-position logits, new_pool).

    One jitted call per prefill chunk: every prefilling lane commits its next
    ``n_tok <= C`` uncached prompt tokens into its own blocks, attending to
    its cached prefix (including prefix-sharing hits in aliased blocks)
    through the block table.  Lanes whose tail completes this chunk read
    their first generated token from the returned logits.
    """
    if isinstance(model, SemanticModel):
        def chunk(params, pool, toks, starts, n_tok, block_tables):
            step = lambda p, c: _paged_chunk_one(
                model.branch, p, c, toks, starts, n_tok, block_tables,
                interpret=interpret)
            logits, new_pool = jax.vmap(step)(params, pool)
            bb, b, v = logits.shape
            return (jnp.transpose(logits, (1, 0, 2)).reshape(b, bb * v),
                    new_pool)
        return chunk

    def chunk(params, pool, toks, starts, n_tok, block_tables):
        return _paged_chunk_one(model, params, pool, toks, starts, n_tok,
                                block_tables, interpret=interpret)

    return chunk


def make_decode_fn(model, *, scan_tokens: int, interpret: bool = False):
    """The fused multi-token decode loop: one jitted dispatch decodes up to
    ``scan_tokens`` greedy tokens for every active lane.

    (params, pool, tok [B,1], block_tables [B,NB], lengths [B],
     remaining [B]) -> (new_pool, tok', lengths', remaining', toks [B, K]).

    ``remaining`` is the per-lane token budget; a lane with remaining == 0 is
    inactive for the rest of the dispatch (null-block writes, frozen length),
    which is what lets heterogeneous ``max_new`` batches share one scan.
    """

    def decode(params, pool, tok, block_tables, lengths, remaining):
        stored = pool

        def step(carry, _):
            pool, tok, lengths, remaining = carry
            active = remaining > 0
            logits, pool = paged_decode_logits(
                model, params, pool, tok, block_tables, lengths, active,
                interpret=interpret)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            tok = jnp.where(active, nxt, tok[:, 0])[:, None]
            lengths = lengths + active.astype(jnp.int32)
            remaining = remaining - active.astype(jnp.int32)
            return (pool, tok, lengths, remaining), nxt

        # the kernel's view for the whole dispatch, so the layer and token
        # loops carry one pool that the kernel updates in place
        carry, toks = jax.lax.scan(
            step, (pages_view(pool), tok, lengths, remaining),
            length=scan_tokens)
        pool, tok, lengths, remaining = carry
        pool = jax.tree.map(lambda x, s: x.reshape(s.shape), pool, stored)
        return pool, tok, lengths, remaining, toks.T

    return decode
