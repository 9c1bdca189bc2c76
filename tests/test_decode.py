"""repro.decode tests: shared paged KV-cache + continuous-batching decode.

Covers the acceptance contract of the paged serving layer:

  * the Pallas paged decode-attention kernel matches the dense XLA reference
    (interpret mode, <= 1e-3), including block tables that ALIAS physical
    blocks across lanes (prefix sharing is read-only for decode),
  * paged-vs-dense numerical parity (same greedy tokens as the legacy
    gang-scheduled dense-cache path),
  * in-flight join parity (a request joining a busy batch at a scan boundary
    decodes the identical tokens to a solo run),
  * prefix-cache parity: a request served via prefix hits + chunked tail
    prefill (including a copy-on-write partial block) produces the identical
    tokens to the same request served cold, on both arms,
  * preemption parity: a lane spilled under pressure and resumed through the
    prefix cache matches its never-preempted run, and a block-pool sized to
    force pressure never rejects a request,
  * the fused scan loop issues <= 1 jitted dispatch per K >= 8 decode tokens,
  * the refcounted block allocator never double-assigns or leaks under
    random alloc/share/register/free (hypothesis property test), frees are
    all-or-nothing accountable, and the null block is never handed out nor
    freeable,
  * recompile-churn accounting is visible via extra_metrics().
"""
import heapq

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.decode import (BlockAllocator, NULL_BLOCK, PagedArmScheduler,
                          PrefixIndex)
from repro.engine import (LAYER, SEMANTIC, FixedPolicy, MABPolicy,
                          PlacementEngine, Request)
from repro.engine.jax_backend import JaxBackend
from paged_decode_cases import check, make_case, run_kernel, run_ref

RNG = np.random.default_rng(0)


# ------------------------------------------------------------------- kernel
def _edge_lengths(bs, nb):
    """Lane lengths at the page edges: empty, one token, a page less one,
    a full page, a page and one, a full lane."""
    return [0, 1, bs - 1, bs, bs + 1, nb * bs]


@pytest.mark.parametrize("h,kh,hd", [(4, 4, 32), (8, 2, 64), (4, 4, 64),
                                     (24, 2, 128)])
@pytest.mark.parametrize("bs,nb", [(4, 4), (8, 2), (16, 2)])
def test_paged_kernel_matches_dense_reference(h, kh, hd, bs, nb):
    """The kernel (interpret mode) on a stacked f32 pool, at a layer other
    than 0: the new token lands at its write slot and nowhere else, and the
    attention through the block table, new token included, matches a dense
    decode-attention reference over the updated pool to <= 1e-3; a lane of
    length 0 (inactive, writing the null block) returns zeros.  MHA and
    GQA up to 12 queries a kv head; the paged oracle holds to the same
    contract."""
    case = make_case(RNG, h, kh, hd, bs, nb, _edge_lengths(bs, nb))
    check(case, *run_kernel(case))
    check(case, *run_ref(case), atol=1e-6, rtol=1e-7)


@pytest.mark.parametrize("h,kh,hd", [(32, 32, 64), (48, 4, 128),
                                     (12, 1, 128)],
                         ids=["mha-hd64", "gqa-rep12-hd128", "one-kv-head"])
def test_paged_kernel_bf16_matches_dense_reference(h, kh, hd):
    """bf16 pools at the served widths' head shapes (stablelm-1.6b's MHA at
    hd 64, starcoder2-15b's 12 queries per kv head at hd 128, and a single
    kv head, whose slot is one row) and the served block size: the pages
    are read in bf16 and attended in f32, so only the bf16 output's
    rounding separates the kernel from the f32 reference."""
    case = make_case(RNG, h, kh, hd, 16, 2, _edge_lengths(16, 2),
                     dtype=jnp.bfloat16)
    check(case, *run_kernel(case), atol=1e-2)


def test_paged_kernel_aliased_block_tables():
    """Prefix sharing makes lanes ALIAS physical blocks: every lane's
    table starts with the same two blocks (a common prompt head) and the
    gather stays correct; each active lane writes only its own tail block,
    so the shared blocks, and an inactive lane's pages, stay bit-identical
    while the inactive lane writes the null block."""
    for h, kh, hd in ((4, 2, 32), (4, 4, 64)):
        case = make_case(RNG, h, kh, hd, 4, 4, [12, 10, 9, 11], alias=2,
                         active=[True, True, True, False])
        check(case, *run_kernel(case))


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_paged_kernel_vmapped_branches(int8):
    """The SEMANTIC arm vmaps the decode step over its branches: the branch
    axis folds into the kernel's grid and pool index, and each branch
    matches its own dense reference and write slots."""
    case = make_case(RNG, 24, 2, 128, 16, 4, [17, 64, 15, 0], int8=int8,
                     branches=2)
    check(case, *run_kernel(case))


@pytest.mark.parametrize("arm", ["layer", "semantic"])
def test_paged_decode_step_kernel_matches_reference(tiny_cfg, arm):
    """A whole paged decode step through the kernel (interpret mode) on the
    lane-dense stacked pool matches the same step through the XLA
    reference: the LAYER model, and the SEMANTIC model whose step vmaps
    over its branches; an inactive lane rides along."""
    from repro.decode.paged_model import pages_view, paged_decode_logits
    from repro.models.model import Model, SemanticModel
    cfg = tiny_cfg.replace(n_heads=4, n_kv_heads=4, head_dim=32)
    model = Model(cfg) if arm == "layer" else SemanticModel(cfg.semantic(2))
    params = model.init(jax.random.PRNGKey(0))
    b, bs, nb = 3, 4, 4
    pool = pages_view(jax.tree.map(
        lambda x: jnp.asarray(RNG.normal(size=x.shape), x.dtype),
        model.init_cache(1 + b * nb, bs)))
    tables = jnp.asarray(RNG.permutation(np.arange(1, 1 + b * nb))
                         .reshape(b, nb), jnp.int32)
    lengths = jnp.asarray([5, 12, 3], jnp.int32)
    active = jnp.asarray([True, True, False])
    tokens = jnp.asarray(RNG.integers(0, cfg.vocab_size, (b, 1)), jnp.int32)
    out = {}
    for interpret in (True, False):
        step = jax.jit(lambda p, c, interpret=interpret: paged_decode_logits(
            model, p, c, tokens, tables, lengths, active,
            interpret=interpret))
        out[interpret] = step(params, pool)
    (got, got_pool), (exp, exp_pool) = out[True], out[False]
    np.testing.assert_allclose(np.asarray(got)[:2], np.asarray(exp)[:2],
                               atol=1e-4, rtol=1e-4)
    for key in exp_pool["pos0"]:
        # block 0 is the inactive lane's scratch
        np.testing.assert_allclose(
            np.asarray(got_pool["pos0"][key])[..., 1:, :, :, :],
            np.asarray(exp_pool["pos0"][key])[..., 1:, :, :, :],
            atol=1e-4, rtol=1e-4, err_msg=key)


# ---------------------------------------------------------------- allocator
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), num_blocks=st.integers(2, 40))
def test_block_allocator_refcounting_never_leaks(seed, num_blocks):
    """Random alloc/share/register/free interleavings under refcounting:
    every handed-out block is exclusively fresh, the null block is never
    handed out, failed allocs are all-or-nothing no-ops, refcounts match the
    live handles exactly, and free + evictable + live is conserved."""
    rng = np.random.default_rng(seed)
    dropped = []
    alloc = BlockAllocator(num_blocks, block_size=4,
                           on_evict=lambda b, k: dropped.append((b, k)))
    handles = []      # each entry holds one reference per block id in it
    total = num_blocks - 1
    for step in range(250):
        op = rng.random()
        if handles and op < 0.35:
            alloc.free(handles.pop(int(rng.integers(len(handles)))))
        elif handles and op < 0.55:
            # a prefix hit: take another reference on live blocks
            ids = list(handles[int(rng.integers(len(handles)))])
            alloc.share(ids)
            handles.append(ids)
        elif handles and op < 0.65:
            # register a live block: when dereferenced it parks as
            # evictable cache instead of returning to the free list
            ids = handles[int(rng.integers(len(handles)))]
            alloc.register(ids[int(rng.integers(len(ids)))], ("key", step))
        else:
            n = int(rng.integers(1, max(2, num_blocks // 2)))
            before = (alloc.free_blocks, alloc.evictable_blocks,
                      alloc.used_blocks)
            ids = alloc.alloc(n)
            if ids is None:
                # all-or-nothing: a failed alloc has NO side effects
                assert n > alloc.available_blocks
                assert before == (alloc.free_blocks, alloc.evictable_blocks,
                                  alloc.used_blocks)
                continue
            assert len(ids) == n and len(set(ids)) == n
            assert NULL_BLOCK not in ids
            live = [b for hs in handles for b in hs]
            assert not set(ids) & set(live), "handed out a live block"
            handles.append(ids)
        # conservation + exact refcounts after every op
        assert (alloc.free_blocks + alloc.evictable_blocks
                + alloc.used_blocks == total)
        counts = {}
        for hs in handles:
            for b in hs:
                counts[b] = counts.get(b, 0) + 1
        assert all(alloc.refcount(b) == c for b, c in counts.items())
    for hs in handles:
        alloc.free(hs)
    assert alloc.used_blocks == 0
    assert alloc.available_blocks == total
    with pytest.raises(ValueError):
        alloc.free([NULL_BLOCK])              # the null block is untouchable
    if total >= 1:
        with pytest.raises(ValueError):
            alloc.free([1])                   # double free is an error
    fresh = BlockAllocator(3, block_size=4)
    with pytest.raises(ValueError):
        fresh.share([1])                      # sharing a free block is too


def test_allocator_shared_block_double_free_guard():
    """A shared block survives its first free (refcount) and a registered
    block parks as evictable, resurrectable by share; over-freeing raises."""
    alloc = BlockAllocator(6, block_size=4)
    ids = alloc.alloc(2)
    alloc.share(ids)                          # second owner
    alloc.free(ids)                           # first owner drops
    assert alloc.used_blocks == 2             # still live via the share
    alloc.register(ids[0], ("k",))
    alloc.free(ids)                           # last owner drops
    assert alloc.used_blocks == 0
    assert alloc.evictable_blocks == 1        # the registered one parked
    assert alloc.free_blocks == 4
    with pytest.raises(ValueError):
        alloc.free([ids[0]])                  # freeing a parked block raises
    alloc.share([ids[0]])                     # ...but a hit resurrects it
    assert alloc.used_blocks == 1


def test_prefix_index_match_and_partial_tail():
    """Chain matching is block-granular and the partial-tail match finds the
    longest common prefix of the first divergent block (never covering the
    whole prompt — >= 1 token is always left to prefill)."""
    idx = PrefixIndex(block_size=4)
    alloc = BlockAllocator(8, block_size=4)
    blocks = alloc.alloc(3)
    hist = np.arange(12)                      # three full blocks
    assert idx.insert(hist, blocks, alloc) == 3
    # same head, diverging inside block 2 -> 2 full + partial R=2
    probe = np.concatenate([np.arange(10), [99, 98]])
    full, tail = idx.match(probe)
    assert full == blocks[:2]
    assert tail == (blocks[2], 2)
    # identical prompt: the last block may NOT cover the final token
    full, tail = idx.match(hist)
    assert full == blocks[:2]
    assert tail == (blocks[2], 3)
    # cold prompt: nothing
    assert idx.match(np.arange(100, 112)) == ([], None)


# ------------------------------------------------------------ decode parity
def _reqs(vocab, n, plen, max_new, seed=5):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, app_id=int(rng.integers(0, 3)),
                    tokens=rng.integers(0, vocab, plen).astype(np.int32),
                    sla_s=float(rng.uniform(0.5, 4.0)), max_new=max_new)
            for i in range(n)]


def _pump(sched, queue, max_steps=300):
    """Drive one arm scheduler to empty: join + chunk prefill + scan."""
    done = []
    steps = 0
    while queue or sched.has_work():
        sched.try_join(queue, 0.0)
        done.extend(sched.prefill_step(0.0))
        done.extend(sched.dispatch(0.0))
        steps += 1
        assert steps < max_steps, "scheduler made no progress"
    return done


def test_paged_matches_dense_decode(tiny_cfg, tiny_mesh):
    """The paged chunked-prefill + scan path produces the same greedy tokens
    as the legacy dense-cache gang path (equal-length prompts, both arms)."""
    for arm in (LAYER, SEMANTIC):
        outs = {}
        for mode in ("paged", "legacy"):
            backend = JaxBackend(tiny_cfg, tiny_mesh, cache_len=16,
                                 max_batch=4, decode=mode, block_size=4,
                                 scan_tokens=4)
            eng = PlacementEngine(FixedPolicy(arm, placement=None), backend)
            reqs = _reqs(tiny_cfg.vocab_size, 3, plen=4, max_new=6)
            eng.submit(reqs)
            eng.drain()
            outs[mode] = [r.output for r in reqs]
            assert all(o.shape == (6,) for o in outs[mode])
        for a, b in zip(outs["paged"], outs["legacy"]):
            np.testing.assert_array_equal(a, b)


def test_in_flight_join_parity(tiny_cfg, tiny_mesh):
    """A request that joins an in-flight decode batch at a scan boundary
    produces the identical token sequence to a solo run — pad tails and the
    shared pool never contaminate a joined sequence."""
    from repro.models.model import build_model

    model = build_model(tiny_cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(9)
    prompt_a = rng.integers(0, tiny_cfg.vocab_size, 5).astype(np.int32)
    prompt_b = rng.integers(0, tiny_cfg.vocab_size, 3).astype(np.int32)
    req = lambda rid, toks, m: Request(rid=rid, app_id=0, tokens=toks,
                                       sla_s=2.0, max_new=m, arrival_s=0.0)

    def run_solo():
        sched = PagedArmScheduler(model, params, n_lanes=4, cache_len=16,
                                  block_size=4, scan_tokens=4)
        q = [(2.0, 0, 0.0, req(0, prompt_a, 6))]
        heapq.heapify(q)
        return _pump(sched, q)[0].out

    def run_joined():
        sched = PagedArmScheduler(model, params, n_lanes=4, cache_len=16,
                                  block_size=4, scan_tokens=4)
        q = [(2.0, 0, 0.0, req(1, prompt_b, 12))]
        heapq.heapify(q)
        sched.try_join(q, 0.0)
        sched.prefill_step(0.0)
        sched.dispatch(0.0)                   # B is mid-flight...
        heapq.heappush(q, (2.0, 1, 0.0, req(0, prompt_a, 6)))
        sched.try_join(q, 0.0)                # ...when A joins
        assert sched.n_active == 2            # the join really was in-flight
        done = _pump(sched, q)
        return next(l.out for l in done if l.req.rid == 0)

    assert run_solo() == run_joined()


def test_prefix_hit_chunked_tail_parity(tiny_cfg, tiny_mesh):
    """A request whose prompt head sits in the prefix cache (full-block hits
    + one copy-on-write partial block) decodes the identical tokens to the
    same request served cold — on both arms."""
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
            scan_tokens=4, prefill_chunk=4)

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
        assert st["used_blocks"] == 0


def test_chunked_prefill_interleaves_with_decode(tiny_cfg, tiny_mesh):
    """A long uncached tail commits in fixed-size chunks, and decode scans
    keep running between chunks — a join wave no longer stalls decode for
    the whole prompt."""
    from repro.models.model import build_model

    model = build_model(tiny_cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(3)
    short = rng.integers(0, tiny_cfg.vocab_size, 3).astype(np.int32)
    long_p = rng.integers(0, tiny_cfg.vocab_size, 16).astype(np.int32)
    req = lambda rid, toks, m: Request(rid=rid, app_id=0, tokens=toks,
                                       sla_s=4.0, max_new=m, arrival_s=0.0)
    sched = PagedArmScheduler(model, params, n_lanes=4, cache_len=32,
                              block_size=4, scan_tokens=2, prefill_chunk=4)
    q = [(4.0, 0, 0.0, req(0, short, 12))]
    heapq.heapify(q)
    sched.try_join(q, 0.0)
    sched.prefill_step(0.0)
    sched.dispatch(0.0)                       # short request is decoding
    heapq.heappush(q, (4.0, 1, 0.0, req(1, long_p, 2)))
    sched.try_join(q, 0.0)                    # long prompt joins
    decoded_before = sched.decoded_tokens
    chunks_before = sched.prefill_chunks
    sched.prefill_step(0.0)                   # chunk 1 of the long tail...
    sched.dispatch(0.0)                       # ...decode proceeds in between
    sched.prefill_step(0.0)                   # chunk 2
    assert sched.prefill_chunks == chunks_before + 2
    assert sched.decoded_tokens > decoded_before
    assert sched.prefill_left[[i for i, l in enumerate(sched.lanes)
                               if l is not None and l.req.rid == 1][0]] > 0
    done = _pump(sched, q)
    assert {l.req.rid for l in done} == {0, 1}


def test_preempt_resume_parity(tiny_cfg, tiny_mesh):
    """Pressure spills the latest-deadline lane (blocks freed, tokens kept
    host-side); its resume re-prefills through the prefix cache and the
    final token sequence matches the never-preempted run exactly."""
    from repro.models.model import build_model

    model = build_model(tiny_cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(9)
    victim_p = rng.integers(0, tiny_cfg.vocab_size, 8).astype(np.int32)
    urgent_p = rng.integers(0, tiny_cfg.vocab_size, 8).astype(np.int32)
    req = lambda rid, toks, m, sla: Request(
        rid=rid, app_id=0, tokens=toks, sla_s=sla, max_new=m, arrival_s=0.0)

    solo = PagedArmScheduler(model, params, n_lanes=2, cache_len=32,
                             block_size=4, scan_tokens=4, prefill_chunk=8)
    q = [(9.0, 0, 0.0, req(0, victim_p, 12, 9.0))]
    heapq.heapify(q)
    want = _pump(solo, q)[0].out

    # pool of 6 allocatable blocks: the victim's 5 + urgent's 3 can't coexist
    sched = PagedArmScheduler(model, params, n_lanes=2, cache_len=32,
                              block_size=4, scan_tokens=4, prefill_chunk=8,
                              num_blocks=7)
    q = [(9.0, 0, 0.0, req(0, victim_p, 12, 9.0))]
    heapq.heapify(q)
    sched.try_join(q, 0.0)
    sched.prefill_step(0.0)
    sched.dispatch(0.0)                       # victim is mid-decode...
    heapq.heappush(q, (1.0, 1, 0.0, req(1, urgent_p, 4, 1.0)))
    done = _pump(sched, q)
    st = sched.stats()
    assert st["preemptions"] >= 1
    assert st["spilled_blocks"] >= 5
    got = next(l.out for l in done if l.req.rid == 0)
    assert got == want
    assert next(l for l in done if l.req.rid == 0).preemptions >= 1
    # the resume's re-prefill hit its own spilled full blocks
    assert st["prefix_hit_tokens"] > 0
    assert st["used_blocks"] == 0


def test_watermark_spills_proactively(tiny_cfg, tiny_mesh):
    """watermark > 0 reserves a headroom fraction: an urgent admission that
    would eat into it spills a later-deadline lane even though the pool is
    not yet exhausted."""
    from repro.models.model import build_model

    model = build_model(tiny_cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(4)
    mk = lambda rid, m, sla: Request(
        rid=rid, app_id=0,
        tokens=rng.integers(0, tiny_cfg.vocab_size, 8).astype(np.int32),
        sla_s=sla, max_new=m, arrival_s=0.0)
    # pool of 12: the loose lane takes 5; urgent needs 5 more — that FITS
    # (7 free), but leaves 2 < watermark reserve 0.5 * 12 = 6 -> spill
    sched = PagedArmScheduler(model, params, n_lanes=4, cache_len=32,
                              block_size=4, scan_tokens=2, prefill_chunk=8,
                              num_blocks=13, watermark=0.5,
                              prefix_sharing=False)
    q = [(9.0, 0, 0.0, mk(0, 12, 9.0))]
    heapq.heapify(q)
    sched.try_join(q, 0.0)
    sched.prefill_step(0.0)
    sched.dispatch(0.0)
    assert sched.alloc.can_alloc(5)           # pool NOT exhausted...
    heapq.heappush(q, (1.0, 1, 0.0, mk(1, 12, 1.0)))
    done = _pump(sched, q)
    assert sched.preemptions >= 1             # ...yet the watermark spilled
    assert {l.req.rid for l in done} == {0, 1}
    assert all(len(l.out) == 12 for l in done)


def test_validate_raise_mid_wave_flushes_pending_cow(tiny_cfg, tiny_mesh):
    """An invalid request popped after a COW admission in the same wave must
    not leave the admitted lane with an unresolved copy (or a leaked pinned
    source ref): the pending COW flushes before the error propagates."""
    from repro.models.model import build_model

    model = build_model(tiny_cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(13)
    head = rng.integers(0, tiny_cfg.vocab_size, 10).astype(np.int32)
    donor = np.concatenate([head, rng.integers(0, tiny_cfg.vocab_size, 2)
                            .astype(np.int32)])
    probe = np.concatenate([head, rng.integers(0, tiny_cfg.vocab_size, 3)
                            .astype(np.int32)])
    req = lambda rid, toks, m: Request(rid=rid, app_id=0, tokens=toks,
                                       sla_s=4.0, max_new=m, arrival_s=0.0)
    make = lambda: PagedArmScheduler(model, params, n_lanes=4, cache_len=32,
                                     block_size=4, scan_tokens=4,
                                     prefill_chunk=4)
    cold = make()
    q = [(4.0, 0, 0.0, req(0, probe, 6))]
    heapq.heapify(q)
    want = _pump(cold, q)[0].out

    sched = make()
    q = [(4.0, 0, 0.0, req(1, donor, 4))]
    heapq.heapify(q)
    _pump(sched, q)                           # cache populated
    oversized = req(2, rng.integers(0, tiny_cfg.vocab_size, 30)
                    .astype(np.int32), 8)     # > per-lane capacity
    q = [(4.0, 0, 0.0, req(0, probe, 6)), (5.0, 1, 0.0, oversized)]
    heapq.heapify(q)
    with pytest.raises(ValueError, match="paged capacity"):
        sched.try_join(q, 0.0)
    assert sched.cow_copies == 1              # the pending copy DID run
    got = _pump(sched, q)
    assert next(l.out for l in got if l.req.rid == 0) == want
    assert sched.alloc.used_blocks == 0       # no leaked pinned source ref


def test_pressure_never_rejects(tiny_cfg, tiny_mesh):
    """A block pool sized to force pressure serves EVERY request: admission
    spills and resumes instead of hard-rejecting, all outputs arrive with
    full budgets, and the extra latency is reported via the preemption
    counters."""
    backend = JaxBackend(tiny_cfg, tiny_mesh, cache_len=32, max_batch=4,
                         block_size=4, scan_tokens=4, num_blocks=13,
                         prefill_chunk=8)
    eng = PlacementEngine(FixedPolicy(LAYER, placement=None), backend)
    rng = np.random.default_rng(11)
    mk = lambda rid, sla, m: Request(
        rid=rid, app_id=0,
        tokens=rng.integers(0, tiny_cfg.vocab_size, 8).astype(np.int32),
        sla_s=sla, max_new=m)
    # two lax lanes fill the 12-block pool (5 blocks each)...
    reqs = [mk(0, 50.0, 12), mk(1, 60.0, 12)]
    eng.submit(reqs)
    eng.step()                                # seated and mid-decode
    # ...then urgent arrivals that cannot fit without spilling them
    reqs += [mk(2, 0.5, 12), mk(3, 0.6, 12)]
    eng.submit(reqs[2:])
    eng.drain()
    m = eng.summary()
    assert m["completed"] == 4                # nobody was rejected
    assert m["preemptions"] >= 1              # and it really was pressured
    assert m["spilled_blocks"] > 0
    assert m["used_blocks"] == 0
    # the spilled lanes' resumes re-prefill through the prefix cache, so
    # hits must be visible at the engine level too
    assert m["prefix_hit_rate"] > 0
    for r in reqs:
        assert r.output.shape == (12,)
    assert eng.stats.preemptions == m["preemptions"]   # EngineStats mirror
    assert eng.stats.spilled_blocks == m["spilled_blocks"]


def test_scan_dispatch_budget(tiny_cfg, tiny_mesh):
    """Acceptance: decode issues <= 1 jitted dispatch per K >= 8 tokens."""
    backend = JaxBackend(tiny_cfg, tiny_mesh, cache_len=32, max_batch=4,
                         block_size=8, scan_tokens=8)
    eng = PlacementEngine(FixedPolicy(LAYER, placement=None), backend)
    reqs = _reqs(tiny_cfg.vocab_size, 3, plen=4, max_new=17)
    eng.submit(reqs)
    eng.drain()
    m = eng.summary()
    assert m["decoded_tokens"] == 3 * 16      # max_new-1 decode tokens each
    # <= 1 dispatch per 8 decode tokens per lane-group: 16 tokens -> 2 scans
    assert m["decode_dispatches"] <= -(-16 // 8)
    assert m["prefill_calls"] == 1            # one wave, one chunk
    for r in reqs:
        assert r.output.shape == (17,)


def test_retire_frees_blocks_and_occupancy_reported(tiny_cfg, tiny_mesh):
    """Finished sequences release their blocks immediately (full ones into
    the evictable prefix cache) and occupancy / pool accounting flows
    through extra_metrics."""
    backend = JaxBackend(tiny_cfg, tiny_mesh, cache_len=16, max_batch=2,
                         block_size=4, scan_tokens=4)
    eng = PlacementEngine(FixedPolicy(LAYER, placement=None), backend)
    reqs = _reqs(tiny_cfg.vocab_size, 5, plen=4, max_new=4)
    eng.submit(reqs)
    eng.drain()
    m = eng.summary()
    assert m["completed"] == 5
    assert m["used_blocks"] == 0              # all references dropped
    assert m["evictable_blocks"] > 0          # retired prefixes stay cached
    assert 0 < m["batch_occupancy"] <= 1
    assert m["compile_decode_misses"] >= 1
    # steady scan length is reused, not recompiled per dispatch
    assert m["compile_decode_hits"] >= 1
    assert m["compile_prefill_misses"] >= 1
    assert m["prefill_calls"] == m["prefill_chunks"]
    # every prompt is distinct here, so nothing can hit the prefix cache —
    # the registered blocks just sit evictable (asserted above)
    assert m["prefix_hit_rate"] == 0.0


def test_legacy_bucket_churn_reported(tiny_cfg, tiny_mesh):
    """The legacy padded-prompt bucketing reports its compilation-cache
    behaviour instead of recompiling silently."""
    backend = JaxBackend(tiny_cfg, tiny_mesh, cache_len=16, max_batch=4,
                         decode="legacy")
    eng = PlacementEngine(FixedPolicy(LAYER, placement=None), backend)
    for seed in (0, 1):
        eng.submit(_reqs(tiny_cfg.vocab_size, 3, plen=4, max_new=2,
                         seed=seed))
        eng.drain()
    m = eng.summary()
    assert m["prefill_bucket_misses"] == 1    # same (arm, b, plen) bucket
    assert m["prefill_bucket_hits"] == 1
    assert m["prefill_buckets"] == {f"arm{LAYER}:b4xs4": 2}


def test_mab_decide_batch_bit_identical():
    """The one-dispatch wave decision replays the sequential key-split
    recurrence exactly (cross-backend decision parity survives batching)."""
    def wave(seed=7, n=9):
        rng = np.random.default_rng(seed)
        return [Request(rid=i, app_id=int(rng.integers(0, 3)),
                        sla_s=float(rng.uniform(0.2, 4.0)))
                for i in range(n)]

    for bandit in ("ucb", "thompson"):
        p_seq = MABPolicy(bandit=bandit, seed=3)
        p_bat = MABPolicy(bandit=bandit, seed=3)
        w_seq, w_bat = wave(), wave()
        assert [p_seq.decide(r) for r in w_seq] == p_bat.decide_batch(w_bat)
        assert [int(r.ctx) for r in w_seq] == [int(r.ctx) for r in w_bat]


def test_paged_capacity_validation(tiny_cfg, tiny_mesh):
    """Requests that can never fit the per-lane paged capacity are rejected
    at submit, not wedged in the queue."""
    backend = JaxBackend(tiny_cfg, tiny_mesh, cache_len=8, max_batch=2,
                         block_size=4)
    eng = PlacementEngine(FixedPolicy(LAYER, placement=None), backend)
    bad = _reqs(tiny_cfg.vocab_size, 1, plen=6, max_new=8)
    with pytest.raises(ValueError, match="paged capacity"):
        eng.submit(bad)
    # a shrunken pool (num_blocks) must also reject at submit: a request
    # that fits a lane but can never fit the pool would wedge the queue
    backend = JaxBackend(tiny_cfg, tiny_mesh, cache_len=32, max_batch=2,
                         block_size=8, num_blocks=3)
    eng = PlacementEngine(FixedPolicy(LAYER, placement=None), backend)
    bad = _reqs(tiny_cfg.vocab_size, 1, plen=8, max_new=16)
    with pytest.raises(ValueError, match="allocatable blocks"):
        eng.submit(bad)
