"""Compile the served path's Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles one kernel at stablelm-1.6b
widths with the TPU compiler that ships with jaxlib, which refuses what the
chip would refuse (block shapes off the (8, 128) tiling, scalar reads from
vector memory, VMEM overflow) and what interpret mode never checks.  The
topology is described inside a fixture, never at import: only one process
at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_decode_attention import paged_decode_attention
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


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_decode_attention_compiles(one_chip, kv_dtype):
    pool, scales = _pool_shapes(kv_dtype)

    def fn(q, k, v, bt, lengths, *kv_scales):
        kw = dict(zip(("k_scale", "v_scale"), kv_scales))
        return paged_decode_attention(q, k, v, bt, lengths, **kw)

    _compile(fn, one_chip, ((B, H, HD), jnp.bfloat16), *pool,
             ((B, NB), jnp.int32), ((B,), jnp.int32), *scales)


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
