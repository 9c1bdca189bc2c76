"""Compile the served path's Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles a served kernel at its
configurations' widths (stablelm-1.6b's, and starcoder2-15b's for the
decode kernel), or the decode step at stablelm-1.6b widths, with the TPU
compiler that ships with jaxlib, which refuses what the chip would refuse
(block shapes off the (8, 128) tiling, scalar reads from vector memory,
VMEM overflow) and what interpret mode never checks.  The
topology is described inside a fixture, never at import: only one process
at a time may load the TPU library.
"""
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_decode_attention import (paged_decode_attention,
                                                  slot_shape)
from repro.kernels.paged_prefill_attention import paged_prefill_attention
from repro.kernels.quant_matmul import quant_matmul

# stablelm-1.6b serving shapes: 8 lanes, 32 heads (MHA), hd 64, a 1024-token
# cache per lane in 16-slot blocks (+ the null block), 32-token prefill
# chunks; d_model 2048
B, H, HD, BS, CACHE_LEN, CHUNK, D = 8, 32, 64, 16, 1024, 32, 2048
NB = CACHE_LEN // BS
POOL = 1 + B * NB


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pool_shapes(kv_dtype):
    pool = [((POOL, BS, H, HD), kv_dtype)] * 2
    scales = [((POOL, BS, H), jnp.float32)] * 2 if kv_dtype == jnp.int8 \
        else []
    return pool, scales


# (layers, query heads, kv heads, head dim) of the two served
# configurations: stablelm-1.6b's 24 MHA layers at hd 64 and the 8 layers of
# starcoder2-15b that one chip holds, 12 queries per kv head at hd 128; and
# one branch of each SEMANTIC arm (half the heads)
DECODE_WIDTHS = {"stablelm-1.6b": (24, 32, 32, 64),
                 "starcoder2-15b": (8, 48, 4, 128),
                 "stablelm-1.6b-sem2": (24, 16, 16, 64),
                 "starcoder2-15b-sem2": (8, 24, 2, 128)}


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_decode_attention_compiles(one_chip, kv_dtype):
    """The decode kernel on the stacked pool of each served configuration
    and SEMANTIC branch, in the view the decode step hands it."""
    for n, h, kh, hd in DECODE_WIDTHS.values():
        leaf = ((n, POOL, BS) + slot_shape(kh, hd, kv_dtype), kv_dtype)
        row = ((B, kh, hd), kv_dtype)
        quant = kv_dtype == jnp.int8
        scale, new_scale = ((n, POOL, BS, kh), jnp.float32), \
            ((B, kh), jnp.float32)

        def fn(q, layer, bt, lengths, wb, wo, k, v, kn, vn, *scales):
            pool, new = {"k": k, "v": v}, {"k": kn, "v": vn}
            if quant:
                pool["k_scale"], pool["v_scale"], new["k_scale"], \
                    new["v_scale"] = scales
            return paged_decode_attention(q, new, pool, layer, bt, lengths,
                                          wb, wo)

        _compile(fn, one_chip, ((B, h, hd), jnp.bfloat16), ((), jnp.int32),
                 ((B, NB), jnp.int32), ((B,), jnp.int32), ((B,), jnp.int32),
                 ((B,), jnp.int32), leaf, leaf, row, row,
                 *([scale, scale, new_scale, new_scale] if quant else []))


def _pool_copies(hlo: str, shapes):
    """(computation, instruction) of every copy, dynamic-slice or
    dynamic-update-slice, alone or fused, whose result has one of
    ``shapes``."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            name = head.group(2)
            comps[name] = []
        elif name and line.strip().startswith(("%", "ROOT")):
            comps[name].append(line)
    moves = ("copy(", "dynamic-slice(", "dynamic-update-slice(")
    found = []
    for comp, lines in comps.items():
        for line in lines:
            m = re.search(r"%([\w.\-]+) = \w+\[([\d,]*)\]", line)
            if not m or m.group(2) not in shapes:
                continue
            called = re.search(r"calls=%([\w.\-]+)", line)
            body = comps.get(called.group(1), []) if called else [line]
            if any(mv in l for l in body for mv in moves):
                found.append((comp, m.group(1)))
    return found


@pytest.mark.parametrize("arm", ["layer", "semantic"])
def test_decode_step_moves_no_pool_per_layer(one_chip, arm):
    """The decode step at stablelm-1.6b widths (2 layers, 8 lanes, 8
    tokens a dispatch) compiled for the chip, for the LAYER model and the
    SEMANTIC one, whose step vmaps over its branches: inside its token and
    layer loops no op copies, slices or writes back a layer's pool, a
    branch's or the stacked pool; the kernel updates it in place.  Only the
    dispatch's edges move the pool, once per K/V leaf each way, between the
    stored layout and the kernel's view."""
    from repro.configs.base import get_config
    from repro.decode.paged_model import make_decode_fn
    from repro.models.model import Model, SemanticModel
    cfg = get_config("stablelm-1.6b").replace(n_layers=2, dtype="bfloat16")
    model = Model(cfg) if arm == "layer" else SemanticModel(cfg.semantic(2))
    n_blocks = 1 + B * NB
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = jax.eval_shape(lambda: model.init_cache(n_blocks, BS))
    put = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), t)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    # the paged forward takes its kernel path on a TPU backend only
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        hlo = jax.jit(make_decode_fn(model, scan_tokens=8),
                      donate_argnums=(1,)).lower(
            put(params), put(pool), i32(B, 1), i32(B, NB), i32(B),
            i32(B)).compile().as_text()
    assert "paged_decode_attention" in hlo
    branch = cfg if arm == "layer" else cfg.semantic(2)
    leaf = jax.tree.leaves(pool)[0].shape     # [(branches,) N, P, bs, K, hd]
    view = leaf[:-2] + slot_shape(branch.n_kv_heads, branch.hd, cfg.dtype)
    # the whole leaf, a branch's stack and one layer's pool, in either view
    shapes = {",".join(map(str, s[k:])) for s in (leaf, view)
              for k in range(len(s) - 3)}
    moves = _pool_copies(hlo, shapes)
    entry = re.search(r"ENTRY %([\w.\-]+) ", hlo).group(1)
    assert [m for m in moves if m[0] != entry] == []
    assert len(moves) <= 4, moves


@pytest.mark.parametrize("chunk", [CHUNK, 1])
@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_prefill_attention_compiles(one_chip, kv_dtype, chunk):
    """The scheduler buckets chunks to powers of two down to 1 token."""
    pool, scales = _pool_shapes(kv_dtype)

    def fn(q, k, v, bt, positions, *kv_scales):
        kw = dict(zip(("k_scale", "v_scale"), kv_scales))
        return paged_prefill_attention(q, k, v, bt, positions, **kw)

    _compile(fn, one_chip, ((B, chunk, H, HD), jnp.bfloat16), *pool,
             ((B, NB), jnp.int32), ((B, chunk), jnp.int32), *scales)


def test_flash_attention_compiles(one_chip):
    qkv = ((1, 2048, H, HD), jnp.bfloat16)
    _compile(flash_attention, one_chip, qkv, qkv, qkv)


@pytest.mark.parametrize("bits", [8, 4])
def test_quant_matmul_compiles(one_chip, bits):
    """Decode-step projection: 8 tokens through a blockwise-quantized
    [2048, 2048] weight (int4 packs two codes per byte)."""
    _compile(quant_matmul, one_chip, ((B, D), jnp.bfloat16),
             ((D * bits // 8, D), jnp.int8), ((D // 128, D), jnp.float32))
