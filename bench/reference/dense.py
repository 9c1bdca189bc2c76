"""Plain float32 reference of a dense decoder served as two split arms.

Straightforward ``jax.numpy`` at ``precision="highest"``: no kernels, no
cache, no batching tricks.  It is computed layer by layer, each layer's
weights drawn inside that layer's call, so it fits on the chip beside
nothing else once the served model is gone.

The equations are the repo's dense decoder (pre-norm LayerNorm blocks,
rotate-half RoPE over the whole head, grouped-query attention, SwiGLU or
tanh-GELU MLP, untied head), which departs from the published models as each
configuration file's ``departures`` says.  The arms:

* LAYER (0): the whole model.
* SEMANTIC (1): ``B`` independent branches, each a full-depth model of
  width d/B with H/B heads, KV/B kv heads, d_ff/B and a vocab/B slice; the
  logits are the branches' slices side by side.  A branch embeds a token id
  clamped into its own vocab/B table (an id past the table reads its last
  row, as an out-of-range gather does).

Weights: random, from the run seed, as the served stack draws them when
built with ``seed``: the root key is ``PRNGKey(seed + 1)``; SEMANTIC splits
it into one key per branch; a model key splits into (embed, stack, -, -);
embed splits into (token table, head, -); the stack splits into one key per
layer, folded with 0, split into (attention, mlp, -, -).  A projection of
fan-in n is N(0, 1) / sqrt(n), the token table N(0, 1) * 0.02, LayerNorm
scale 1 and bias 0; every weight is rounded to the served dtype.

``fp8=True`` is the lower-precision control: every projection's weights
(per output column) and inputs (per token) are rounded to float8 e4m3
with an absmax scale before the float32 matmul.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LAYER, SEMANTIC = 0, 1
F8_MAX = 448.0


def _ceil_div(x: int, b: int) -> int:
    return -(-x // b)


def branch_dims(cfg: dict, arm: int) -> dict:
    """Widths of one model of the arm (the whole model, or one branch)."""
    d, h, kv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    ff, vocab = cfg["d_ff"], cfg["vocab_size"]
    hd = cfg["head_dim"]
    b = 1
    if arm == SEMANTIC:
        b = cfg["serving"]["semantic_branches"]
        d, ff, vocab = _ceil_div(d, b), _ceil_div(ff, b), _ceil_div(vocab, b)
        h, kv = max(1, _ceil_div(h, b)), max(1, _ceil_div(kv, b))
    return dict(branches=b, d=d, h=h, kv=kv, hd=hd, ff=ff, vocab=vocab,
                n_layers=cfg["n_layers"], mlp=cfg["mlp_type"],
                theta=float(cfg["rope_theta"]), eps=float(cfg["norm_eps"]),
                dtype=cfg["dtype"])


def _draw(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype).astype(
        jnp.float32)


def _dense(key, d_in, d_out, dtype):
    return _draw(key, (d_in, d_out), 1.0 / math.sqrt(d_in), dtype)


def _q8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return x @ w


def _layernorm(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    w = jnp.ones((x.shape[-1],), jnp.float32)
    b = jnp.zeros((x.shape[-1],), jnp.float32)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _rope(x, pos, theta):
    """x [S, H, hd]; rotate-half over the whole head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs          # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, pos, dims):
    """One sequence: q [S, H, hd], k/v [S, KV, hd], causal."""
    rep = dims["h"] // dims["kv"]
    k = jnp.repeat(k, rep, axis=1)          # head i reads kv head i // rep
    v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(dims["hd"])
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


@partial(jax.jit, static_argnames=("dims", "fp8"))
def _layer(x, key, *, dims, fp8):
    """One pre-norm block over x [N, S, d], its weights drawn from ``key``."""
    dims = dict(dims)
    d, h, kv, hd, ff, dt = (dims[k] for k in
                            ("d", "h", "kv", "hd", "ff", "dtype"))
    ks = jax.random.split(jax.random.fold_in(key, 0), 4)
    ka = jax.random.split(ks[0], 4)
    wq, wk = _dense(ka[0], d, h * hd, dt), _dense(ka[1], d, kv * hd, dt)
    wv, wo = _dense(ka[2], d, kv * hd, dt), _dense(ka[3], h * hd, d, dt)
    n, s, _ = x.shape
    pos = jnp.arange(s)
    hn = _layernorm(x, dims["eps"])
    q = _mm(hn, wq, fp8).reshape(n, s, h, hd)
    k = _mm(hn, wk, fp8).reshape(n, s, kv, hd)
    v = _mm(hn, wv, fp8).reshape(n, s, kv, hd)

    def one(args):
        q1, k1, v1 = args
        return _attention(_rope(q1, pos, dims["theta"]),
                          _rope(k1, pos, dims["theta"]), v1, pos, dims)

    att = jax.lax.map(one, (q, k, v)).reshape(n, s, h * hd)
    x = x + _mm(att, wo, fp8)
    hn = _layernorm(x, dims["eps"])
    if dims["mlp"] == "swiglu":
        km = jax.random.split(ks[1], 3)
        wg, wu, wd = (_dense(km[0], d, ff, dt), _dense(km[1], d, ff, dt),
                      _dense(km[2], ff, d, dt))
        y = jax.nn.silu(_mm(hn, wg, fp8)) * _mm(hn, wu, fp8)
    else:
        km = jax.random.split(ks[1], 2)
        wu, wd = _dense(km[0], d, ff, dt), _dense(km[1], ff, d, dt)
        y = jax.nn.gelu(_mm(hn, wu, fp8), approximate=True)
    return x + _mm(y, wd, fp8)


@partial(jax.jit, static_argnames=("dims",))
def _embed(tokens, key, *, dims):
    dims = dict(dims)
    k_tok = jax.random.split(key, 3)[0]
    table = _draw(k_tok, (dims["vocab"], dims["d"]), 0.02, dims["dtype"])
    return table[jnp.clip(tokens, 0, dims["vocab"] - 1)]


@partial(jax.jit, static_argnames=("dims", "fp8"))
def _head(x, pos, key, *, dims, fp8):
    dims = dict(dims)
    k_head = jax.random.split(key, 3)[1]
    head = _dense(k_head, dims["d"], dims["vocab"], dims["dtype"])
    x = _layernorm(x, dims["eps"])
    x = jnp.take_along_axis(x, pos[:, :, None], axis=1)     # [N, T, d]
    return _mm(x, head, fp8)


def logits_at(cfg: dict, arm: int, seed: int, tokens: np.ndarray,
              pos: np.ndarray, *, fp8: bool = False) -> jax.Array:
    """Reference logits of the arm at positions ``pos`` [N, T] of the
    token rows ``tokens`` [N, S]: [N, T, vocab] float32 on the device."""
    dims = branch_dims(cfg, arm)
    static = tuple(sorted(dims.items()))
    root = jax.random.PRNGKey(seed + 1)
    keys = [root] if arm == LAYER else list(
        jax.random.split(root, dims["branches"]))
    toks = jnp.asarray(tokens, jnp.int32)
    where = jnp.asarray(pos, jnp.int32)
    out = []
    with jax.default_matmul_precision("highest"):
        for key in keys:
            k_embed, k_stack, _, _ = jax.random.split(key, 4)
            x = _embed(toks, k_embed, dims=static)
            for lk in jax.random.split(k_stack, dims["n_layers"]):
                x = _layer(x, lk, dims=static, fp8=fp8)
            out.append(_head(x, where, k_embed, dims=static, fp8=fp8))
            del x
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=-1)
