#!/usr/bin/env python3
"""Chip smoke test: drive the served path once on a TPU and check it.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the cross-chip paths only

One chip: stablelm-1.6b at its published widths in bfloat16 (random weights
from a seed), served through the same ``PlacementEngine`` -> ``JaxBackend``
-> ``PagedArmScheduler`` -> Pallas paged kernels stack that
``repro.launch.serve`` builds, on the default two split arms (LAYER,
SEMANTIC) with prefix sharing on.  It checks

  * the paged decode and chunked-prefill kernels against their
    ``repro.kernels.ref`` references at the served shapes, on the chip;
  * that every seeded request completes with its full ``max_new`` tokens,
    none rejected or failed, every token id inside the vocabulary;
  * that the prefill logits of the legacy path (``runner.prefill_into_cache``,
    XLA attention) are finite, and reports (does not gate) how many first
    tokens agree with the paged path.

``--chips 4`` runs only what exists across chips, each against its one-chip
comparison: the disaggregated fleet (prefill and decode workers on distinct
chips, KV blocks shipped by ``shard_map``/``ppermute``) against the
colocated one-chip run on the same requests, and ``1f1b`` layer-split
training on a (1, 4) mesh against ``fsdp`` on the same step-0 batch.

Nothing here falls back: without a TPU the script exits non-zero before any
phase runs, kernels run compiled (never interpreted), and a failed check
exits non-zero.  The last line of stdout, on success only, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "stablelm-1.6b"
CACHE_LEN = 1024
MAX_BATCH = 8
N_REQUESTS = 16
PROMPT_LENS = (64, 128, 256, 512)
HEAD_LEN = 128               # shared prompt head: 8 blocks of 16 slots
# kernel vs reference on bf16 inputs and outputs: both accumulate in f32 and
# round once to bf16, so they may differ by a couple of bf16 ulps (2^-7
# relative near 1) from summation order
KERNEL_ATOL = 2e-2
KERNEL_RTOL = 2e-2
# step-0 loss, 1f1b vs fsdp (float32 params; TPU matmuls at default
# precision, reduction order differs between the two programs)
LOSS_RTOL = 2e-3

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration


# ------------------------------------------------------------------ inputs
def make_requests(vocab: int, seed: int, n: int = N_REQUESTS):
    """Seeded requests: prompts of 64-512 tokens, max_new 16-32.  Odd
    requests (loose SLA) open with one of two shared 128-token prompt heads
    (256/512-token prompts); even ones (tight SLA) are independent."""
    from repro.engine import Request
    rng = np.random.default_rng(seed)
    heads = [rng.integers(0, vocab, HEAD_LEN) for _ in range(2)]
    reqs = []
    for i in range(n):
        if i % 2:
            plen = PROMPT_LENS[2 + (i // 2) % 2]
            toks = rng.integers(0, vocab, plen)
            toks[:HEAD_LEN] = heads[(i // 4) % 2]
        else:
            plen = PROMPT_LENS[(i // 2) % len(PROMPT_LENS)]
            toks = rng.integers(0, vocab, plen)
        reqs.append(Request(rid=i, app_id=int(rng.integers(3)),
                            tokens=toks.astype(np.int32),
                            sla_s=0.05 if i % 2 == 0 else 5.0,
                            max_new=int(rng.integers(16, 33))))
    return reqs


def clone(reqs):
    """Fresh copies of requests (same tokens, budgets and decisions)."""
    from repro.engine import Request
    return [Request(rid=r.rid, app_id=r.app_id, tokens=r.tokens.copy(),
                    sla_s=r.sla_s, max_new=r.max_new, decision=r.decision)
            for r in reqs]


# ----------------------------------------------------------------- phases
def kernel_parity(cfg, seed: int) -> None:
    """Compiled paged kernels vs their references at the served shapes:
    LAYER (the full model's heads) and SEMANTIC (one branch's heads).  The
    decode kernel runs on a two-layer stacked pool in the decode step's
    view, at layer 1; its pool writes must equal the reference's."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.decode.paged_model import pages_view
    from repro.kernels.paged_decode_attention import paged_decode_attention
    from repro.kernels.paged_prefill_attention import paged_prefill_attention

    sem = cfg.semantic(2)
    bs = 16
    nb = CACHE_LEN // bs
    n_blocks = 1 + MAX_BATCH * nb
    chunk = 32
    rng = np.random.default_rng(seed)
    for arm, c in (("layer", cfg), ("semantic", sem)):
        h, kh, hd = c.n_heads, c.n_kv_heads, c.hd
        bf = lambda *s: jnp.asarray(rng.normal(size=s), jnp.bfloat16)
        k_pool, v_pool = bf(n_blocks, bs, kh, hd), bf(n_blocks, bs, kh, hd)
        table_np = (1 + rng.permutation(n_blocks - 1)).reshape(MAX_BATCH, nb)
        tables = jnp.asarray(table_np, jnp.int32)
        lens_np = rng.integers(1, CACHE_LEN + 1, MAX_BATCH)
        lengths = jnp.asarray(lens_np, jnp.int32)
        starts = rng.integers(0, CACHE_LEN - chunk + 1, MAX_BATCH)
        positions = jnp.asarray(starts[:, None] + np.arange(chunk), jnp.int32)
        last = lens_np - 1
        stacked = pages_view({"k": jnp.stack([k_pool, bf(*k_pool.shape)]),
                              "v": jnp.stack([v_pool, bf(*v_pool.shape)])})
        new = {"k": bf(MAX_BATCH, kh, hd), "v": bf(MAX_BATCH, kh, hd)}
        decode_args = (bf(MAX_BATCH, h, hd), new, stacked, jnp.int32(1),
                       tables, lengths,
                       jnp.asarray(table_np[np.arange(MAX_BATCH), last // bs],
                                   jnp.int32),
                       jnp.asarray(last % bs, jnp.int32))
        cases = (
            ("paged_decode_attention", paged_decode_attention,
             ref.paged_decode_attention_ref, decode_args),
            ("paged_prefill_attention", paged_prefill_attention,
             ref.paged_prefill_attention_ref,
             (bf(MAX_BATCH, chunk, h, hd), k_pool, v_pool, tables,
              positions)),
        )
        for name, kernel, reference, args in cases:
            out, exp = jax.jit(kernel)(*args), jax.jit(reference)(*args)
            if isinstance(out, tuple):
                (out, pool), (exp, exp_pool) = out, exp
                for key in pool:
                    check(bool(jnp.array_equal(pool[key], exp_pool[key])),
                          f"{name} ({arm}) wrote {key} unlike its reference")
            out = np.asarray(out, np.float32)
            exp = np.asarray(exp, np.float32)
            err = float(np.max(np.abs(out - exp)))
            print(f"kernel {name} [{arm}: H={h} K={kh} hd={hd} "
                  f"pool={n_blocks}x{bs}]: max_abs_err={err:.3e}",
                  flush=True)
            check(bool(np.isfinite(out).all()), f"{name} ({arm}) not finite")
            check(bool(np.all(np.abs(out - exp)
                              <= KERNEL_ATOL + KERNEL_RTOL * np.abs(exp))),
                  f"{name} ({arm}) differs from its reference beyond "
                  f"atol={KERNEL_ATOL} rtol={KERNEL_RTOL}")


def serve_requests(eng, reqs, clock: CompileClock, label: str):
    """Submit ``reqs`` in two waves (the second finds the first's prompt
    heads in the prefix cache), drain, and check every outcome."""
    t_compile0 = clock.seconds
    t0 = time.perf_counter()
    outcomes = []
    half = len(reqs) // 2
    for wave in (reqs[:half], reqs[half:]):
        eng.submit(wave)
        outcomes += eng.drain()
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - t_compile0
    check(len(outcomes) == len(reqs),
          f"{label}: {len(outcomes)} of {len(reqs)} requests completed")
    vocab = eng.backend.cfg.vocab_size
    for o in outcomes:
        r = o.request
        check(not (o.failed or o.shed), f"{label}: request {r.rid} failed")
        check(r.output is not None and len(r.output) == r.max_new,
              f"{label}: request {r.rid} produced "
              f"{0 if r.output is None else len(r.output)} of "
              f"{r.max_new} tokens")
        check(bool(np.all((r.output >= 0) & (r.output < vocab))),
              f"{label}: request {r.rid} emitted a token id outside the "
              "vocabulary")
    s = eng.summary()
    tokens = sum(len(o.request.output) for o in outcomes)
    arms = {}
    for o in outcomes:
        arms[o.decision] = arms.get(o.decision, 0) + 1
    print(f"{label}: {len(outcomes)} requests, {tokens} tokens generated, "
          f"per-arm {dict(sorted(arms.items()))}, "
          f"prefix_hit_rate={s.get('prefix_hit_rate')}, "
          f"preemptions={s.get('preemptions')}", flush=True)
    print(f"{label}: wall_s={wall:.3f} compile_s={compile_s:.3f} "
          f"run_s={wall - compile_s:.3f} (host clock; compile_s sums JAX "
          "trace+lower+compile events)", flush=True)
    return {o.request.rid: o for o in outcomes}


def legacy_first_tokens(backend, outcomes) -> None:
    """First token of each request through the legacy path's prefill
    (``runner.prefill_into_cache``, XLA attention) on the same params; the
    logits must be finite, the agreement with the paged path is reported."""
    import jax
    import jax.numpy as jnp
    fns = {}
    agree = 0
    for rid, o in sorted(outcomes.items()):
        arm = o.decision
        runner = backend.runners[arm]
        if arm not in fns:
            fns[arm] = jax.jit(runner.prefill_into_cache)
        cache = runner.init_cache(1, backend.cache_len)
        logits, _ = fns[arm](backend.params[arm], cache,
                             jnp.asarray(o.request.tokens)[None])
        logits = np.asarray(logits, np.float32)
        check(bool(np.isfinite(logits).all()),
              f"legacy prefill logits of request {rid} not finite")
        agree += int(np.argmax(logits[0])) == int(o.request.output[0])
        del cache
    print(f"first tokens paged vs legacy (XLA attention) prefill: "
          f"{agree}/{len(outcomes)} agree ({agree / len(outcomes):.3f}; "
          "reported, not gated)", flush=True)


def one_chip(cfg, seed: int, clock: CompileClock) -> None:
    import jax
    from repro.launch.serve import build_engine
    from repro.launch.train import make_mesh

    t0 = time.perf_counter()
    c0 = clock.seconds
    kernel_parity(cfg, seed)
    print(f"kernel parity: {time.perf_counter() - t0:.3f}s "
          f"(compile_s={clock.seconds - c0:.3f})", flush=True)

    t0 = time.perf_counter()
    eng = build_engine(cfg, make_mesh("1,1"), cache_len=CACHE_LEN,
                       max_batch=MAX_BATCH)
    print(f"engine built: {time.perf_counter() - t0:.3f}s; arms "
          f"{sorted(eng.backend.runners)}", flush=True)
    outcomes = serve_requests(eng, make_requests(cfg.vocab_size, seed),
                              clock, "served")
    legacy_first_tokens(eng.backend, outcomes)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)


def placements(backend) -> str:
    """Which chip holds each scheduler's params and pool."""
    import jax
    out = []
    for arm, (pf, dc, _) in sorted(backend._disagg.items()):
        for role, s in (("prefill", pf), ("decode", dc)):
            pdev = {d.id for x in jax.tree.leaves(s.params)
                    for d in x.devices()}
            kdev = {d.id for x in jax.tree.leaves(s.pool)
                    for d in x.devices()}
            out.append(f"arm{arm}.{role}: params@{sorted(pdev)} "
                       f"pool@{sorted(kdev)}")
    return "; ".join(out)


def four_chips(cfg, seed: int, clock: CompileClock) -> None:
    import jax
    from repro.launch.serve import build_engine
    from repro.launch.train import make_mesh, train

    devs = jax.devices()
    # 1f1b layer split on (1, 4) vs fsdp: the published widths, depth cut
    # to one layer per stage
    tcfg = cfg.replace(n_layers=4)
    mesh = make_mesh("1,4")
    kw = dict(steps=3, seq_len=256, batch=8, lr=3e-4, log_every=1)
    pipe = train(tcfg, mesh, mode="pipeline", schedule="1f1b", **kw)
    ref = train(tcfg, mesh, mode="fsdp", **{**kw, "steps": 1})
    rel = abs(pipe[0] - ref[0]) / abs(ref[0])
    print(f"1f1b step-0 loss {pipe[0]:.6f} vs fsdp {ref[0]:.6f}: "
          f"rel_diff={rel:.3e} (tolerance {LOSS_RTOL}); 1f1b losses "
          f"{[round(x, 6) for x in pipe]}", flush=True)
    check(bool(np.isfinite(pipe).all()), "1f1b losses not finite")
    check(rel <= LOSS_RTOL, "1f1b step-0 loss differs from fsdp")

    # disaggregated fleet vs colocated on one chip, same requests and arms
    reqs = make_requests(cfg.vocab_size, seed)
    eng = build_engine(cfg, make_mesh("1,1"), cache_len=CACHE_LEN,
                       max_batch=MAX_BATCH)
    colo = serve_requests(eng, reqs, clock, "colocated (1 chip)")
    del eng
    gc.collect()
    again = clone(reqs)               # keep the colocated run's decisions
    eng = build_engine(cfg, make_mesh("1,1"), cache_len=CACHE_LEN,
                       max_batch=MAX_BATCH, fleet="disagg",
                       fleet_devices=devs)
    where = placements(eng.backend)
    print(f"disagg placement: {where}", flush=True)
    for arm, (pf, dc, _) in eng.backend._disagg.items():
        check(pf.device is not None and dc.device is not None
              and pf.device != dc.device,
              f"arm {arm}: prefill and decode workers share a chip")
    dis = serve_requests(eng, again, clock, "disagg (4 chips)")
    st = eng.summary()
    print(f"disagg: blocks_shipped={st.get('blocks_shipped')} "
          f"transfer_bytes={st.get('transfer_bytes')}", flush=True)
    same = sum(np.array_equal(colo[r].request.output, dis[r].request.output)
               for r in colo)
    print(f"disagg vs colocated tokens: {same}/{len(colo)} requests equal",
          flush=True)
    check(same == len(colo), "disagg tokens differ from colocated")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU; JAX's first device is "
              f"{devs[0].platform!r}", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 2
    from repro.configs.base import get_config
    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device}; compile cache {cache_dir}", flush=True)
    cfg = get_config(ARCH).replace(dtype="bfloat16")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(cfg, args.seed, clock)
        else:
            one_chip(cfg, args.seed, clock)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total: {time.perf_counter() - t0:.3f}s "
          f"(compile_s={clock.seconds:.3f})", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
