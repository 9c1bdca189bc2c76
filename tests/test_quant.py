"""Quantized serving tests: blockwise int8/int4 weight GEMM, the int8
paged-KV block layout, and the Pallas chunked-prefill attention kernel.

Covers the acceptance contract of the quantized path:

  * blockwise quantize/dequantize round-trips within the symmetric bound
    (|w - deq(q)| <= scale/2 per element, hypothesis property, both widths),
  * the Pallas dequant-in-register GEMM matches the dequantize-then-matmul
    XLA reference (interpret mode) for int8 and packed int4,
  * the Pallas chunked-prefill attention kernel matches the dense-gather
    XLA reference on f32 AND int8 pools (GQA, shuffled block tables,
    mid-sequence chunk starts),
  * the paged decode kernel's int8 dequant epilogue matches its reference,
  * ``copy_blocks`` moves int8 codes + per-slot scales bit-exactly (COW
    never requantizes),
  * int8-KV serving parity: a prefix-cache-hit request decodes the IDENTICAL
    tokens to the same request served cold, on both arms — quantize-on-write
    is a pure function of the token's K/V, so shared blocks replay exactly,
  * the ``kv_dtype``/``weight_quant`` knobs surface capacity + error
    telemetry through scheduler stats.
"""
import heapq

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.decode import (PagedArmScheduler, copy_blocks,
                          int8_kv_capacity_ratio, pool_block_bytes,
                          quantize_kv, quantize_pool)
from repro.engine import Request
from repro.kernels import ref
from repro.kernels.paged_prefill_attention import paged_prefill_attention
from repro.kernels.quant_matmul import (dequantize_blockwise, quant_matmul,
                                        quantize_blockwise)

from paged_decode_cases import check, make_case, run_kernel, run_ref

RNG = np.random.default_rng(0)


# ------------------------------------------------------- quantize round-trip
@settings(max_examples=25, deadline=None)
@given(d=st.sampled_from([32, 128, 256]), e=st.integers(1, 6),
       bits=st.sampled_from([8, 4]), seed=st.integers(0, 2**31 - 1))
def test_blockwise_roundtrip_error_bound(d, e, bits, seed):
    """Symmetric blockwise quantization round-trips within half a step:
    |w - dequant(quant(w))| <= scale/2 element-wise, for int8 and int4."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(scale=rng.uniform(0.1, 3.0), size=(d, 8 * e)),
                    jnp.float32)
    q, s = quantize_blockwise(w, bits=bits)
    deq = dequantize_blockwise(q, s, bits=bits)
    err = np.abs(np.asarray(deq) - np.asarray(w))
    g = d // s.shape[-2]
    bound = np.repeat(np.asarray(s), g, axis=-2) / 2 + 1e-7
    assert (err <= bound).all(), (bits, float(err.max()))


def test_blockwise_zero_group_safe():
    """An all-zero group gets scale 0 and decodes to exact zeros (the
    freshly initialized pool / padded weights case)."""
    w = jnp.zeros((256, 16), jnp.float32)
    for bits in (8, 4):
        q, s = quantize_blockwise(w, bits=bits)
        assert not np.asarray(s).any()
        assert not np.asarray(dequantize_blockwise(q, s, bits=bits)).any()


# ------------------------------------------------------------- quant GEMM
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("t,d,e", [(64, 256, 128), (16, 64, 256)])
def test_quant_matmul_kernel_matches_ref(bits, t, d, e):
    """The Pallas dequant-in-register GEMM (interpret mode) matches the
    dequantize-then-matmul XLA reference for both bit widths."""
    x = jnp.asarray(RNG.normal(size=(t, d)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(d, e)), jnp.float32)
    q, s = quantize_blockwise(w, bits=bits)
    out = quant_matmul(x, q, s, interpret=True)
    exp = ref.quant_matmul_ref(x, q, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-4,
                               rtol=2e-4)


def test_quant_matmul_tracks_f32():
    """int8 GEMM stays close to the f32 matmul it approximates: the error is
    bounded by sum over groups of (group scale / 2) x sum |x| per group."""
    x = jnp.asarray(RNG.normal(size=(32, 256)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(256, 64)), jnp.float32)
    q, s = quantize_blockwise(w, bits=8)
    out = np.asarray(quant_matmul(x, q, s, interpret=True))
    f32 = np.asarray(x @ w)
    g = 256 // s.shape[-2]
    xa = np.abs(np.asarray(x)).reshape(32, -1, g).sum(-1)   # [T, n_groups]
    bound = xa @ (np.asarray(s) / 2) + 1e-4                 # [T, E]
    assert (np.abs(out - f32) <= bound).all()


def test_ops_quant_matmul_interpret_override():
    """The jit'd ops wrapper takes the explicit interpret override like
    every other op, and ``use_kernels(False)`` routes to the oracle."""
    from repro.kernels import ops
    x = jnp.asarray(RNG.normal(size=(16, 128)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(128, 32)), jnp.float32)
    q, s = quantize_blockwise(w, bits=8)
    exp = ref.quant_matmul_ref(x, q, s)
    for kw in ({"interpret": True}, {"interpret": None}, {}):
        np.testing.assert_allclose(np.asarray(ops.quant_matmul(x, q, s, **kw)),
                                   np.asarray(exp), atol=2e-4, rtol=2e-4)
    ops.use_kernels(False)
    try:
        # jit reassociates the oracle's reductions: allclose, not bit-equal
        np.testing.assert_allclose(np.asarray(ops.quant_matmul(x, q, s)),
                                   np.asarray(exp), atol=1e-4, rtol=1e-4)
    finally:
        ops.use_kernels(True)


# ------------------------------------------------ chunked-prefill kernel
@pytest.mark.parametrize("h,kh,hd", [(4, 4, 32), (8, 2, 64)])
@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_prefill_kernel_matches_ref(h, kh, hd, kv_dtype):
    """The Pallas chunked-prefill attention kernel (block-table gather +
    in-chunk causal triangle, interpret mode) matches the dense-gather XLA
    reference — GQA, shuffled physical blocks, mid-sequence chunk starts,
    on f32 and int8 pools."""
    b, c, bs, nb = 3, 8, 4, 4
    p_blocks = 1 + b * nb
    q = jnp.asarray(RNG.normal(size=(b, c, h, hd)), jnp.float32)
    kp = jnp.asarray(RNG.normal(size=(p_blocks, bs, kh, hd)), jnp.float32)
    vp = jnp.asarray(RNG.normal(size=(p_blocks, bs, kh, hd)), jnp.float32)
    perm = RNG.permutation(np.arange(1, p_blocks))
    bt = jnp.asarray(perm.reshape(b, nb).astype(np.int32))
    starts = jnp.asarray(RNG.integers(0, nb * bs - c + 1, b), jnp.int32)
    pos = starts[:, None] + jnp.arange(c)[None, :]
    scales = {}
    if kv_dtype == "int8":
        kp, ks = quantize_kv(kp)
        vp, vs = quantize_kv(vp)
        scales = {"k_scale": ks, "v_scale": vs}
    out = paged_prefill_attention(q, kp, vp, bt, pos, interpret=True,
                                  **scales)
    exp = ref.paged_prefill_attention_ref(q, kp, vp, bt, pos, **scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=1e-3,
                               rtol=1e-3)


def test_decode_kernel_int8_matches_ref():
    """The paged decode kernel's int8 dequant matches the reference's
    dequantize-then-gather, and the new token's codes and per-slot scales
    land at its write slot and nowhere else."""
    case = make_case(RNG, 4, 2, 32, 4, 4, [7, 16, 1, 0], int8=True)
    check(case, *run_kernel(case))
    check(case, *run_ref(case), atol=1e-6, rtol=1e-7)


@pytest.mark.parametrize("h,kh,hd,alias", [(8, 8, 64, 0), (24, 2, 128, 1)],
                         ids=["mha-hd64", "gqa-rep12-hd128-aliased"])
def test_decode_kernel_int8_served_head_shapes(h, kh, hd, alias):
    """int8 pools at the served head shapes: MHA at hd 64 (two heads to a
    128-lane row, each dequantized by its own scale) and 12 queries per kv
    head at hd 128 over a shared prefix block (active lanes write past
    it); lengths at the page edges."""
    case = make_case(RNG, h, kh, hd, 16, 3, [17, 32, 33, 48, 0],
                     int8=True, alias=alias)
    check(case, *run_kernel(case))


# ------------------------------------------------------ int8 block layout
def test_quantized_pool_copy_blocks_bit_exact():
    """COW on an int8 pool copies codes AND per-slot scales verbatim — a
    copied block dequantizes to exactly the source block's values."""
    p, bs, kh, hd = 6, 4, 2, 8
    pool = {"layer0": {
        "k": jnp.asarray(RNG.normal(size=(p, bs, kh, hd)), jnp.float32),
        "v": jnp.asarray(RNG.normal(size=(p, bs, kh, hd)), jnp.float32)}}
    qpool = quantize_pool(pool)
    kq, ks = quantize_kv(pool["layer0"]["k"])
    qpool["layer0"]["k"] = kq
    qpool["layer0"]["k_scale"] = ks
    out = copy_blocks(qpool, jnp.asarray([1, 3]), jnp.asarray([4, 5]))
    np.testing.assert_array_equal(np.asarray(out["layer0"]["k"][4]),
                                  np.asarray(kq[1]))
    np.testing.assert_array_equal(np.asarray(out["layer0"]["k_scale"][5]),
                                  np.asarray(ks[3]))
    # untouched blocks stay untouched
    np.testing.assert_array_equal(np.asarray(out["layer0"]["k"][2]),
                                  np.asarray(kq[2]))


def test_int8_pool_capacity():
    """The int8 layout shrinks a block by 4*hd/(hd+4): >= 1.9x effective
    capacity for every hd >= 4, ~3.56x at hd=32."""
    assert int8_kv_capacity_ratio(32) == pytest.approx(128 / 36)
    assert all(int8_kv_capacity_ratio(hd) >= 1.9 for hd in (4, 8, 32, 128))
    pool = {"k": jnp.zeros((5, 4, 2, 32), jnp.float32),
            "v": jnp.zeros((5, 4, 2, 32), jnp.float32)}
    f32_b = pool_block_bytes(pool)
    int8_b = pool_block_bytes(quantize_pool(pool))
    assert f32_b / int8_b == pytest.approx(int8_kv_capacity_ratio(32))


# --------------------------------------------------------- serving parity
def _pump(sched, queue, max_steps=300):
    done = []
    steps = 0
    while queue or sched.has_work():
        sched.try_join(queue, 0.0)
        done.extend(sched.prefill_step(0.0))
        done.extend(sched.dispatch(0.0))
        steps += 1
        assert steps < max_steps, "scheduler made no progress"
    return done


def test_int8_kv_prefix_hit_parity(tiny_cfg, tiny_mesh):
    """int8-KV serving is deterministic under prefix sharing: a request whose
    prompt head hits shared quantized blocks (incl. a COW partial block)
    decodes the IDENTICAL tokens to the same request served cold, on both
    arms — quantize-on-write commits the same codes+scales either way and
    COW copies them bit-exactly."""
    from repro.dist import api as A

    rng = np.random.default_rng(13)
    head = rng.integers(0, tiny_cfg.vocab_size, 10).astype(np.int32)
    donor = np.concatenate([head, rng.integers(0, tiny_cfg.vocab_size, 2)
                            .astype(np.int32)])
    probe = np.concatenate([head, rng.integers(0, tiny_cfg.vocab_size, 3)
                            .astype(np.int32)])
    req = lambda rid, toks, m: Request(rid=rid, app_id=0, tokens=toks,
                                       sla_s=4.0, max_new=m, arrival_s=0.0)
    for mode in ("pipeline", "semantic"):
        runner = A.build_runner(tiny_cfg, mode, tiny_mesh)
        params = runner.init(jax.random.PRNGKey(2))
        make = lambda: PagedArmScheduler(
            runner.model, params, n_lanes=4, cache_len=32, block_size=4,
            scan_tokens=4, prefill_chunk=4, kv_dtype="int8")

        cold = make()
        q = [(4.0, 0, 0.0, req(0, probe, 6))]
        heapq.heapify(q)
        want = _pump(cold, q)[0].out

        warm = make()
        q = [(4.0, 0, 0.0, req(1, donor, 4))]
        heapq.heapify(q)
        _pump(warm, q)                        # donor populates the cache
        q = [(4.0, 1, 0.0, req(0, probe, 6))]
        heapq.heapify(q)
        got = _pump(warm, q)[0].out
        st = warm.stats()
        assert st["prefix_hit_tokens"] >= 8   # two full head blocks shared
        assert st["cow_copies"] >= 1          # block 2 diverges mid-block
        assert got == want, f"{mode}: warm {got} != cold {want}"
        assert st["kv_capacity_x"] >= 1.9


def test_scheduler_quant_knob_validation_and_telemetry(tiny_cfg, tiny_mesh):
    """Bad knob values raise; good ones surface capacity/error telemetry
    through stats(), and weight quantization never mutates the caller's
    f32 params."""
    from repro.dist import api as A

    runner = A.build_runner(tiny_cfg, "pipeline", tiny_mesh)
    params = runner.init(jax.random.PRNGKey(2))
    make = lambda **kw: PagedArmScheduler(
        runner.model, params, n_lanes=2, cache_len=16, block_size=4,
        scan_tokens=4, prefill_chunk=4, **kw)
    with pytest.raises(ValueError, match="kv_dtype"):
        make(kv_dtype="fp8")
    with pytest.raises(ValueError, match="weight_quant"):
        make(weight_quant="int2")

    wq0 = np.asarray(params["blocks"]["pos0"]["mix"]["wq"]).copy()
    sched = make(kv_dtype="int8", weight_quant="int4")
    st = sched.stats()
    assert st["kv_capacity_x"] >= 1.9
    assert st["kv_block_bytes"] < st["kv_block_bytes_f32"]
    assert st["weight_quant_bits"] == 4
    assert st["weight_quant_max_err"] > 0
    # the shared f32 params are untouched — the scheduler quantized a copy
    np.testing.assert_array_equal(
        np.asarray(params["blocks"]["pos0"]["mix"]["wq"]), wq0)
    assert isinstance(sched.params["blocks"]["pos0"]["mix"]["wq"], dict)

    # quantized end-to-end smoke: requests complete with sane outputs
    reqs = [Request(rid=i, app_id=0,
                    tokens=np.arange(1, 6, dtype=np.int32) * (i + 1),
                    sla_s=4.0, max_new=3, arrival_s=0.0) for i in range(2)]
    q = [(4.0, i, 0.0, r) for i, r in enumerate(reqs)]
    heapq.heapify(q)
    done = _pump(sched, q)
    assert sorted(len(l.out) for l in done) == [3, 3]
