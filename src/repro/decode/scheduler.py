"""Continuous-batching scheduler over the shared paged KV pool of one arm.

Replaces the legacy gang-scheduled batch (form batch -> prefill -> decode to
the longest request -> retire all) with persistent decode *lanes*:

  * ``try_join``     admits queued requests into free lanes at a scan
    boundary (EDF order).  With prefix sharing on, the cached head of each
    prompt maps onto existing physical blocks (refcount shares; a partially
    matching block is resolved with one copy-on-write block copy), so only
    the uncached tail needs prefill.  Under allocator pressure the scheduler
    *preempts*: latest-deadline victim lanes spill their blocks back to the
    pool (prompt + generated tokens stay host-side, full blocks stay
    matchable in the prefix index) instead of the join hard-rejecting.
  * ``prefill_step`` commits ONE fixed-size chunk of uncached prompt tokens
    per prefilling lane — one jitted call across the wave — so a long tail
    never stalls decode for more than a chunk between scans.
  * ``dispatch``     runs one fused ``lax.scan`` decode call (K tokens per
    jitted dispatch) across the decoding lanes; lanes that exhaust their
    budget mid-scan go inactive and are retired immediately afterwards,
    returning (or prefix-caching) their blocks — no waiting for the batch's
    longest request.

Spilled lanes re-enter through ``try_join`` as resume candidates: their
re-prefill covers prompt + generated-so-far and itself hits the prefix
cache, so a preemption costs roughly one chunked tail re-prefill.

Compilation is bounded: prefill chunks key on (pow2 wave width, chunk),
decode dispatches on (pow2 lane width, pow2 scan length), COW copies on the
pow2 pair count; the scheduler counts hits/misses per bucket so benchmarks
can see recompile churn (``compile_stats``).
"""
from __future__ import annotations

import heapq
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.decode.paged_cache import (NULL_BLOCK, BlockAllocator, PrefixIndex,
                                      copy_blocks, pool_block_bytes,
                                      quantize_pool)
from repro.decode.paged_model import (make_decode_fn, make_prefill_chunk_fn,
                                      quantize_attn_params,
                                      supports_paged_decode)
from repro.engine.types import next_pow2
from repro.obs import Histogram, get_tracer


@dataclass
class Lane:
    """Host-side record of one in-flight (or spilled) sequence."""
    req: object
    enq: float
    join_t: float
    blocks: List[int]
    out: List[int] = field(default_factory=list)
    n_shared: int = 0            # leading block-table entries from the index
    preemptions: int = 0
    committed: int = 0           # cache slots filled when detached for ship
    first_tok_t: float = 0.0     # wall-clock of the first generated token
    seat_t: float = 0.0          # host clock at this seating
    prefill_busy_s: float = 0.0  # chunk waves run for it since seating

    @property
    def deadline(self) -> float:
        base = self.req.arrival_s if self.req.arrival_s is not None \
            else self.enq
        return base + self.req.sla_s

    def history(self) -> np.ndarray:
        """prompt + generated tokens — position p of the sequence holds
        ``history()[p]`` (the resume-prefill input after a preemption)."""
        out = np.asarray(self.out, np.int32)
        return np.concatenate([np.asarray(self.req.tokens, np.int32), out])


class PagedArmScheduler:
    """Paged continuous-batching state for one split arm's model/params."""

    #: metric kinds for ``stats()`` keys (``repro.obs.metrics``): everything
    #: undeclared is a flow counter and SUMS across schedulers; gauges are
    #: per-pool layout properties that MAX; ratios recompute from the merged
    #: counters so cross-arm aggregates stay token-weighted.  This replaces
    #: the old suffix-keyed "max-not-sum" list in JaxBackend.extra_metrics.
    STAT_KINDS = {
        "batch_occupancy": ("ratio", "decoded_tokens", "lane_steps"),
        "mean_active_lanes": ("ratio", "active_lane_frac_sum",
                              "decode_dispatches"),
        "prefix_hit_rate": ("ratio", "prefix_hit_tokens",
                            "prefix_query_tokens"),
        "kv_block_bytes": "gauge",
        "kv_block_bytes_f32": "gauge",
        "kv_capacity_x": "gauge",
        "weight_quant_bits": "gauge",
        "weight_quant_max_err": "gauge",
        "weight_quant_mean_err": "gauge",
    }

    def __init__(self, model, params, *, n_lanes: int, cache_len: int,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 scan_tokens: int = 8, util_floor: float = 0.5,
                 prefill_chunk: int = 32, prefix_sharing: bool = True,
                 watermark: float = 0.0, interpret: bool = False,
                 kv_dtype: str = "f32", weight_quant: Optional[str] = None,
                 role: str = "colocated", device=None, clock=None,
                 jit_cache: Optional[dict] = None):
        if not supports_paged_decode(model):
            raise ValueError("model does not support paged decode "
                             "(needs pure global-attention mixers)")
        if role not in ("colocated", "prefill", "decode"):
            raise ValueError(f"role must be 'colocated', 'prefill' or "
                             f"'decode', got {role!r}")
        if kv_dtype not in ("f32", "int8"):
            raise ValueError(f"kv_dtype must be 'f32' or 'int8', "
                             f"got {kv_dtype!r}")
        if weight_quant not in (None, "int8", "int4"):
            raise ValueError(f"weight_quant must be None, 'int8' or 'int4', "
                             f"got {weight_quant!r}")
        self.model = model
        self.role = role
        self.device = device
        self.clock = clock
        # the prefill counters' host clock: the owner's, else perf_counter
        self._clock = clock if clock is not None else time.perf_counter
        # trace track: (process, thread) labels for this scheduler's span
        # row in the Chrome trace; JaxBackend overwrites with arm labels
        dev = device if device is not None else jax.devices()[0]
        self.track = ("paged", f"{role}@{dev}")
        self.kv_dtype = kv_dtype
        self.weight_quant = weight_quant
        self.quant_telemetry: Dict[str, float] = {}
        if weight_quant is not None:
            # quantize a PRIVATE copy of the attention projections — the
            # caller's f32 params stay untouched (other arms / legacy paths
            # may share them)
            params, self.quant_telemetry = quantize_attn_params(
                params, int(weight_quant[3:]))
        self.params = params
        self.n_lanes = n_lanes
        self.block_size = block_size
        self.scan_tokens = scan_tokens
        self.util_floor = util_floor
        self.prefill_chunk = prefill_chunk
        self.prefix_sharing = prefix_sharing
        self.watermark = watermark
        self.interpret = interpret
        self.max_blocks = -(-cache_len // block_size)
        if num_blocks is None:
            # full capacity: every lane can hold cache_len tokens, + null
            num_blocks = 1 + n_lanes * self.max_blocks
        self.index = PrefixIndex(block_size)
        self.alloc = BlockAllocator(
            num_blocks, block_size,
            on_evict=lambda blk, key: self.index.drop(key))
        self.pool = model.init_cache(num_blocks, block_size)
        self.kv_block_bytes_f32 = pool_block_bytes(self.pool)
        if kv_dtype == "int8":
            # int8 codes + one f32 scale per (token slot, kv head): the
            # scatter/attend paths key on the "k_scale" leaves
            self.pool = quantize_pool(self.pool)
        self.kv_block_bytes = pool_block_bytes(self.pool)
        if device is not None:
            # a fleet worker: pin params and pool to its device so every
            # jitted prefill/decode call runs (and keeps its outputs) there
            self.params = jax.device_put(self.params, device)
            self.pool = jax.device_put(self.pool, device)

        self.block_tables = np.full((n_lanes, self.max_blocks), NULL_BLOCK,
                                    np.int32)
        self.lengths = np.zeros(n_lanes, np.int32)      # committed tokens
        self.prefill_left = np.zeros(n_lanes, np.int32)
        self.remaining = np.zeros(n_lanes, np.int32)    # decode budget
        self.last_tok = np.zeros(n_lanes, np.int32)
        self.lanes: List[Optional[Lane]] = [None] * n_lanes
        self._resume: list = []       # (deadline, seq, lane) heap of spills
        self._rseq = 0
        self._ready: List[Lane] = []  # prefill role: detached, ship-ready

        # compiled-program cache, keyed (kind,) + shape bucket.  A fleet of
        # replicas serving the SAME arm passes one shared dict so each
        # bucket compiles once fleet-wide (programs are pure functions of
        # params/pool shapes, which replicas of an arm share); distinct
        # arms must never share one (different models).
        self._jitted: Dict[tuple, object] = \
            jit_cache if jit_cache is not None else {}

        # instrumentation
        self.join_waves = 0
        self.joined = 0
        self.prefill_chunks = 0
        self.decode_dispatches = 0
        self.decoded_tokens = 0
        self.lane_steps = 0            # lanes x scan length, all dispatches
        self._active_frac_sum = 0.0   # running mean, not an unbounded list
        self.prefix_hit_tokens = 0
        self.prefix_query_tokens = 0
        # per lane that took its first token: seat -> first token, and the
        # chunk waves that carried it (dispatch through readback); their
        # difference is the time its prefill stalled between chunks
        self.prefill_wait_lane_s = 0.0
        self.prefill_busy_lane_s = 0.0
        self.cow_copies = 0
        self.preemptions = 0
        self.spilled_blocks = 0
        # fault-recovery telemetry: full re-executions forced on this
        # scheduler's lanes (blackout evacuations, backpressure evictions),
        # fault-disrupted requests re-admitted here, and the fault ->
        # re-admission latency distribution (merged up by the backend)
        self.re_executions = 0
        self.recovered = 0
        self.recovery_latency = Histogram()
        self.compile_stats: Dict[str, int] = {}
        self.buckets: Dict[str, int] = {}

    # ----------------------------------------------------------- capacity
    def max_tokens_per_seq(self) -> int:
        return self.max_blocks * self.block_size

    def validate(self, req) -> None:
        # a prefill-only worker holds the prompt (and ships it before the
        # first decode write); the decode side needs the full final length
        if self.role == "prefill":
            need = len(req.tokens)
        else:
            need = len(req.tokens) + max(int(req.max_new), 1) - 1
        if need > self.max_tokens_per_seq():
            raise ValueError(
                f"request {req.rid}: {need} cache slots exceed the per-lane "
                f"paged capacity {self.max_tokens_per_seq()}")
        if self.alloc.blocks_for(need) > self.alloc.num_blocks - 1:
            raise ValueError(
                f"request {req.rid}: needs {self.alloc.blocks_for(need)} "
                f"blocks but the arm pool has {self.alloc.num_blocks - 1} "
                "allocatable blocks — it could never be admitted")

    @property
    def n_active(self) -> int:
        return sum(l is not None for l in self.lanes)

    @property
    def backlog(self) -> int:
        """Seated lanes + spilled lanes awaiting resume + ship-ready."""
        return self.n_active + len(self._resume) + len(self._ready)

    def has_free_lane(self) -> bool:
        return any(l is None for l in self.lanes)

    def earliest_deadline(self) -> Optional[float]:
        live = [l.deadline for l in self.lanes if l is not None]
        live += [l.deadline for l in self._ready]
        if self._resume:
            live.append(self._resume[0][0])
        return min(live) if live else None

    def has_work(self) -> bool:
        return self.backlog > 0

    def _scan_bucket(self, rems: np.ndarray) -> int:
        """Scan length for this dispatch: the largest pow2 <= scan_tokens
        whose slot utilization (sum min(rem, k) / (n_act * k)) stays above
        ``util_floor``.  Homogeneous budgets get the full fused scan (the
        <= 1 dispatch per K tokens contract); a heavily mixed batch shortens
        the scan instead of burning slots on lanes that retire mid-scan."""
        best = 1
        k = 1
        n = len(rems)
        while k <= self.scan_tokens:
            if float(np.minimum(rems, k).sum()) >= self.util_floor * n * k:
                best = k
            k *= 2
        return min(best, next_pow2(int(rems.max())))

    # --------------------------------------------------------------- jit
    def _get_jitted(self, kind: str, key: tuple, build, donate=(1,)):
        full = (kind,) + key
        stat = f"{kind}_hits" if full in self._jitted else f"{kind}_misses"
        self.compile_stats[stat] = self.compile_stats.get(stat, 0) + 1
        name = f"{kind}:{'x'.join(map(str, key))}"
        if full not in self._jitted:
            # the pool is fully rewritten every call: donate it so the
            # device never holds two copies.  CPU has no donation support
            # and would warn per call.
            dn = donate if jax.default_backend() != "cpu" else ()
            self._jitted[full] = jax.jit(build(), donate_argnums=dn)
            get_tracer().instant("compile_miss", track=self.track,
                                 bucket=name)
        self.buckets[name] = self.buckets.get(name, 0) + 1
        return self._jitted[full]

    # ------------------------------------------------------- release/spill
    def _release(self, li: int, *, register: bool) -> int:
        """Retire or spill the lane in slot ``li``: register the full blocks
        of its committed history in the prefix index (so later prompts — and
        its own resume — hit them), then drop all block references.  Returns
        the number of references released."""
        lane = self.lanes[li]
        written = int(self.lengths[li])
        if register and self.prefix_sharing and written >= self.block_size:
            self.index.insert(lane.history()[:written], lane.blocks,
                              self.alloc)
        n = len(lane.blocks)
        if lane.blocks:
            # park tail-first: LRU eviction then reclaims chain TAILS before
            # their parents, so the surviving shorter prefix stays matchable
            # (an evicted parent would orphan still-parked descendants)
            self.alloc.free(lane.blocks[::-1])
        lane.blocks = []
        lane.n_shared = 0
        self.lanes[li] = None
        self.block_tables[li] = NULL_BLOCK
        self.lengths[li] = 0
        self.prefill_left[li] = 0
        self.remaining[li] = 0
        return n

    def _preempt(self, li: int, now: float) -> None:
        """Spill the lane: blocks go back to the pool (full ones stay
        matchable), prompt + generated tokens stay host-side, and the lane
        queues for resume — its re-prefill runs back through the prefix
        cache."""
        lane = self.lanes[li]
        released = self._release(li, register=True)
        lane.preemptions += 1
        self.preemptions += 1
        self.spilled_blocks += released
        get_tracer().instant("preempt", track=self.track, req=lane.req.rid,
                             spilled=released)
        heapq.heappush(self._resume, (lane.deadline, self._rseq, lane))
        self._rseq += 1

    def _spill_until(self, n_needed: int, deadline: float, now: float) -> None:
        """Preempt latest-deadline victims until ``n_needed`` blocks (plus
        the watermark headroom) are available or no strictly-later-deadline
        victim remains.  Never spills a lane to serve a less urgent one."""
        reserve = int(self.watermark * (self.alloc.num_blocks - 1))
        while self.alloc.available_blocks < n_needed + reserve:
            victims = [(l.deadline, li) for li, l in enumerate(self.lanes)
                       if l is not None and l.deadline > deadline]
            if not victims:
                return
            self._preempt(max(victims)[1], now)

    # ---------------------------------------------------- fault recovery
    def _observe_recovery(self, lane: Lane, now: float) -> None:
        """A fault-disrupted request just re-seated: close its recovery arc
        (fault stamp -> re-admission) and clear the stamp."""
        req = lane.req
        if req.fault_t <= 0.0:
            return
        self.recovery_latency.observe(max(now - req.fault_t, 0.0))
        self.recovered += 1
        req.fault_t = 0.0
        get_tracer().instant("recovery", track=self.track, req=req.rid)

    @staticmethod
    def reset_for_reexec(lane: Lane) -> None:
        """Host-side reset to pre-prefill state: the request will re-execute
        from scratch (deterministic argmax decode -> bit-identical tokens)."""
        lane.out = []
        lane.blocks = []
        lane.n_shared = 0
        lane.committed = 0
        lane.first_tok_t = 0.0

    def spill_all(self, now: float, fault_t: Optional[float] = None) -> int:
        """Blackout response for a colocated/prefill scheduler: preempt every
        seated lane through the ordinary spill path — blocks park in the
        prefix cache, lanes queue for resume, and the arm drains nothing
        until the owner re-enables it.  Returns the number spilled."""
        seated = [li for li, l in enumerate(self.lanes) if l is not None]
        for li in seated:
            if fault_t is not None:
                self.lanes[li].req.fault_t = fault_t
            self._preempt(li, now)
        return len(seated)

    def evacuate(self, now: float,
                 fault_t: Optional[float] = None) -> List[Lane]:
        """Blackout response for a decode scheduler: seated lanes cannot
        resume here (they seat via ``admit_shipped``), so each is fully
        reset for re-execution — blocks go back (full ones stay matchable,
        making the re-ship a receiver-side prefix hit) and the caller
        requeues the requests for a fresh prefill."""
        out: List[Lane] = []
        for li, lane in enumerate(self.lanes):
            if lane is None:
                continue
            self._release(li, register=True)
            self.reset_for_reexec(lane)
            if fault_t is not None:
                lane.req.fault_t = fault_t
            self.re_executions += 1
            out.append(lane)
        return out

    def evict_latest(self, deadline: float, now: float) -> Optional[Lane]:
        """Ship-backpressure preemption: reset the seated lane with the
        LATEST deadline strictly later than ``deadline`` so an arriving
        (more urgent) shipment can seat / allocate.  The victim re-executes
        from prefill (its blocks stay matchable — the re-ship prefix-hits).
        Returns the evicted lane for requeue, or None if every seated lane
        is at least as urgent."""
        victims = [(l.deadline, li) for li, l in enumerate(self.lanes)
                   if l is not None and l.deadline > deadline]
        if not victims:
            return None
        li = max(victims)[1]
        lane = self.lanes[li]
        self._release(li, register=True)
        self.reset_for_reexec(lane)
        self.preemptions += 1
        self.re_executions += 1
        get_tracer().instant("decode_spill", track=self.track,
                             req=lane.req.rid)
        return lane

    # -------------------------------------------------------------- joins
    def try_join(self, queue: list, now: float) -> None:
        """Admit the most urgent queued/spilled candidates into free lanes
        at a scan boundary.  Each admission maps its cached prompt head onto
        shared blocks, resolves at most one copy-on-write block, and
        allocates private blocks for the rest — spilling later-deadline
        lanes under pressure.  No model dispatch happens here; the seated
        lanes prefill chunk-by-chunk via ``prefill_step``."""
        if self.role == "decode":
            raise RuntimeError("decode-role scheduler seats lanes via "
                               "admit_shipped, not try_join")
        if not (queue or self._resume):
            return
        free = [i for i, l in enumerate(self.lanes) if l is None]
        # the span records the wave even when an admission's validate()
        # raises mid-loop (the context manager exits on the exception path)
        with get_tracer().span("join_wave", track=self.track,
                               free=len(free)) as sp:
            admitted = self._join_wave(queue, now, free)
            sp.set(admitted=admitted)

    def _join_wave(self, queue: list, now: float, free: List[int]) -> int:
        tr = get_tracer()
        t_seat = self._clock()
        seat = iter(free)
        cow_pairs: List[tuple] = []
        admitted = 0
        while admitted < len(free) and (queue or self._resume):
            use_resume = bool(self._resume) and (
                not queue or self._resume[0][0] <= queue[0][0])
            if use_resume:
                _, _, lane = heapq.heappop(self._resume)
            else:
                item = heapq.heappop(queue)
                _, _, enq, req = item
                # direct callers may not have gone through backend.submit's
                # validation; an impossible request must raise, not wedge —
                # but earlier admissions of this wave may have COW copies
                # pending, and their lanes already count the copied tokens
                # as cached: flush before propagating
                try:
                    self.validate(req)
                except ValueError:
                    self._flush_cow(cow_pairs)
                    raise
                lane = Lane(req=req, enq=enq, join_t=now, blocks=[])
            req = lane.req
            seq_toks = lane.history()
            if self.role == "prefill":
                # prompt slots only: the first decode write happens on the
                # receiver, after the blocks ship
                total_need = self.alloc.blocks_for(len(seq_toks))
            else:
                total_need = self.alloc.blocks_for(
                    len(req.tokens) + max(int(req.max_new), 1) - 1)
            shared: List[int] = []
            cow = None
            if self.prefix_sharing:
                shared, cow = self.index.match(seq_toks)
            if shared:
                self.alloc.share(shared)
            if cow is not None:
                # pin the COW source so allocating this lane's private
                # blocks cannot evict it before the copy runs
                self.alloc.share([cow[0]])
            n_alloc = total_need - len(shared)
            # watermark reserve makes pressure PROACTIVE: spilling starts
            # once an admission would eat into the headroom fraction, not
            # only after the pool is already exhausted
            reserve = int(self.watermark * (self.alloc.num_blocks - 1))
            if self.alloc.available_blocks < n_alloc + reserve:
                self._spill_until(n_alloc, lane.deadline, now)
            ids = self.alloc.alloc(n_alloc)
            if ids is None and cow is not None:
                # borderline pool: drop the COW pin and retry without it
                self.alloc.free([cow[0]])
                cow = None
                self._spill_until(n_alloc, lane.deadline, now)
                ids = self.alloc.alloc(n_alloc)
            if ids is None:
                # pool exhausted and every seated lane is more urgent: the
                # candidate waits (blocks drain as lanes retire) — admission
                # never hard-rejects
                if shared:
                    self.alloc.free(shared)
                if use_resume:
                    heapq.heappush(self._resume,
                                   (lane.deadline, self._rseq, lane))
                    self._rseq += 1
                else:
                    heapq.heappush(queue, item)
                break
            covered = len(shared) * self.block_size
            if cow is not None:
                src, keep = cow
                cow_pairs.append((src, ids[0]))
                covered += keep
            lane.blocks = shared + ids
            lane.n_shared = len(shared)
            lane.seat_t = t_seat
            lane.prefill_busy_s = 0.0
            li = next(seat)
            self.lanes[li] = lane
            row = np.full(self.max_blocks, NULL_BLOCK, np.int32)
            row[:len(lane.blocks)] = lane.blocks
            self.block_tables[li] = row
            self.lengths[li] = covered
            self.prefill_left[li] = len(seq_toks) - covered
            self.remaining[li] = 0
            self.prefix_hit_tokens += covered
            self.prefix_query_tokens += len(seq_toks)
            tr.instant("seat", req=req.rid, cached=covered,
                       resumed=use_resume)
            self._observe_recovery(lane, now)
            admitted += 1

        self._flush_cow(cow_pairs)
        if admitted:
            self.join_waves += 1
            self.joined += admitted
        return admitted

    def _flush_cow(self, cow_pairs: List[tuple]) -> None:
        """Run the wave's pending copy-on-write block copies (one jitted,
        pow2-bucketed call) and release the pinned source references."""
        if not cow_pairs:
            return
        n_pad = next_pow2(len(cow_pairs))
        src = np.full(n_pad, NULL_BLOCK, np.int32)
        dst = np.full(n_pad, NULL_BLOCK, np.int32)
        for i, (s, d) in enumerate(cow_pairs):
            src[i], dst[i] = s, d
        fn = self._get_jitted("cow", (n_pad,),
                              lambda: copy_blocks, donate=(0,))
        with get_tracer().span("cow_copy", track=self.track,
                               label=f"cow:{n_pad}", pairs=len(cow_pairs)):
            self.pool = fn(self.pool, jnp.asarray(src), jnp.asarray(dst))
        self.cow_copies += len(cow_pairs)
        # copies done — the pinned sources can go back to the cache
        self.alloc.free([s for s, _ in cow_pairs])
        cow_pairs.clear()

    # ------------------------------------------------------------ prefill
    def prefill_step(self, now: float) -> List[Lane]:
        """Commit ONE chunk of uncached prompt tokens for every prefilling
        lane (one jitted call, pow2 wave width).  Lanes whose tail completes
        read their first generated token from the chunk logits; a lane whose
        budget is already spent (max_new covered by resume history, or
        max_new == 1) retires here.  Returns the retired lanes."""
        pf = [i for i, l in enumerate(self.lanes)
              if l is not None and self.prefill_left[i] > 0]
        if not pf:
            return []
        tr = get_tracer()
        with tr.span("prefill_prep", track=self.track):
            w = next_pow2(len(pf))
            # chunk length buckets to the widest lane's need (pow2, capped
            # at prefill_chunk) — prefix-cache hits leave short tails, and
            # an 8-token tail must not pay a chunk-wide dispatch
            c = min(self.prefill_chunk,
                    next_pow2(int(min(np.max(self.prefill_left[pf]),
                                      self.prefill_chunk))))
            toks = np.zeros((w, c), np.int32)
            starts = np.zeros(w, np.int32)
            n_tok = np.zeros(w, np.int32)
            bt = np.full((w, self.max_blocks), NULL_BLOCK, np.int32)
            for row, li in enumerate(pf):
                lane = self.lanes[li]
                s0 = int(self.lengths[li])
                k = min(int(self.prefill_left[li]), c)
                toks[row, :k] = lane.history()[s0:s0 + k]
                starts[row] = s0
                n_tok[row] = k
                bt[row] = self.block_tables[li]
            fn = self._get_jitted(
                "prefill", (w, c),
                lambda: make_prefill_chunk_fn(self.model,
                                              interpret=self.interpret))
        rids = [self.lanes[li].req.rid for li in pf] if tr.enabled else None
        t0 = self._clock()
        with tr.span("prefill_chunk", track=self.track,
                     label=f"prefill:{w}x{c}", wave=len(pf), chunk=c,
                     rids=rids):
            logits, self.pool = fn(self.params, self.pool, jnp.asarray(toks),
                                   jnp.asarray(starts), jnp.asarray(n_tok),
                                   jnp.asarray(bt))
            with tr.span("prefill_readback"):
                first = np.asarray(jnp.argmax(logits, axis=-1)).astype(
                    np.int32)
        t1 = self._clock()
        self.prefill_chunks += 1

        retired: List[Lane] = []
        t_first = t1 if self.clock is not None else now
        for row, li in enumerate(pf):
            lane = self.lanes[li]
            lane.prefill_busy_s += t1 - t0
            k = min(int(self.prefill_left[li]), c)
            self.lengths[li] += k
            self.prefill_left[li] -= k
            if self.prefill_left[li] > 0:
                continue
            self.prefill_wait_lane_s += t1 - lane.seat_t
            self.prefill_busy_lane_s += lane.prefill_busy_s
            lane.out.append(int(first[row]))
            lane.first_tok_t = t_first
            tr.instant("first_token", track=self.track, req=lane.req.rid)
            budget = int(lane.req.max_new) - len(lane.out)
            if budget <= 0:
                self._release(li, register=True)
                retired.append(lane)
                tr.instant("retire", track=self.track, req=lane.req.rid)
            elif self.role == "prefill":
                # detach for shipping: the lane keeps its block references,
                # the seat frees for the next prefill wave.  The cache store
                # ships the blocks and calls ``finish_shipped``.
                lane.committed = int(self.lengths[li])
                self._detach(li)
                self._ready.append(lane)
            else:
                self.remaining[li] = budget
                self.last_tok[li] = first[row]
        return retired

    # ----------------------------------------------------- ship / receive
    def _detach(self, li: int) -> None:
        """Clear seat ``li`` WITHOUT dropping the lane's block references —
        the detached lane still owns its blocks (contrast ``_release``)."""
        self.lanes[li] = None
        self.block_tables[li] = NULL_BLOCK
        self.lengths[li] = 0
        self.prefill_left[li] = 0
        self.remaining[li] = 0

    def take_ready(self) -> List[Lane]:
        """Drain the ship-ready lanes a prefill worker has detached."""
        out, self._ready = self._ready, []
        return out

    def finish_shipped(self, lane: Lane) -> None:
        """Source-side epilogue of a shipment: register the lane's full
        blocks in this worker's prefix index (later same-head prompts skip
        their re-prefill), then drop the block references."""
        if self.prefix_sharing and lane.committed >= self.block_size:
            self.index.insert(lane.history()[:lane.committed], lane.blocks,
                              self.alloc)
        if lane.blocks:
            self.alloc.free(lane.blocks[::-1])
        lane.blocks = []
        lane.n_shared = 0

    def admit_shipped(self, lane: Lane, now: float) -> None:
        """Seat an arrived shipment in a free decode lane.  ``lane.blocks``
        already names physically-local blocks (the cache store rewrote the
        table on receive), so decoding resumes from the first generated
        token at position ``committed`` exactly as the colocated path
        would: first decode write lands at slot ``committed``."""
        if self.role != "decode":
            raise RuntimeError("admit_shipped on a non-decode scheduler")
        li = next(i for i, l in enumerate(self.lanes) if l is None)
        if self.prefix_sharing and lane.committed >= self.block_size:
            # shipped blocks become cached prefix HERE: the next same-head
            # request hits the receiver's index and skips the transfer
            self.index.insert(lane.history()[:lane.committed], lane.blocks,
                              self.alloc)
        self.lanes[li] = lane
        row = np.full(self.max_blocks, NULL_BLOCK, np.int32)
        row[:len(lane.blocks)] = lane.blocks
        self.block_tables[li] = row
        self.lengths[li] = lane.committed
        self.prefill_left[li] = 0
        self.remaining[li] = int(lane.req.max_new) - len(lane.out)
        self.last_tok[li] = lane.out[-1]
        self.joined += 1
        get_tracer().instant("admit_shipped", track=self.track,
                             req=lane.req.rid, blocks=len(lane.blocks))
        self._observe_recovery(lane, now)

    # ------------------------------------------------------------ dispatch
    def dispatch(self, now: float) -> List[Lane]:
        """One fused scan decode across the decoding lanes; retire finished
        lanes.  Returns the retired lanes (callers stamp Outcomes).

        Active lanes are compacted into a pow2-width dispatch (empty lanes
        cost nothing) and the scan length buckets to the largest remaining
        budget — both bounded compile keys, both counted in
        ``compile_stats``.

        Split into ``dispatch_async`` (enqueue the jitted scan, return
        immediately with device futures) + ``finish_dispatch`` (block on the
        results, retire) so a disagg driver can hide the ship wave behind
        the running scan.
        """
        return self.finish_dispatch(self.dispatch_async(now), now)

    def dispatch_async(self, now: float) -> Optional[dict]:
        """Enqueue one fused scan decode and return WITHOUT reading any
        result off the device.  The returned pending record holds the
        output futures plus enough host state to retire lanes later; pass
        it to ``finish_dispatch``.  Returns None when no lane is decoding.

        ``self.pool`` is rebound to the scan's output future right away, so
        work enqueued between the two halves (e.g. a cache-store ship wave)
        consumes the post-scan pool — device programs serialize per queue,
        which is exactly what makes the overlap safe."""
        act = np.nonzero(self.remaining > 0)[0]
        n_act = len(act)
        if n_act == 0:
            return None
        tr = get_tracer()
        with tr.span("decode_prep", track=self.track):
            w = next_pow2(n_act)
            k_eff = self._scan_bucket(self.remaining[act])
            fn = self._get_jitted(
                "decode", (w, k_eff),
                lambda: make_decode_fn(self.model, scan_tokens=k_eff,
                                       interpret=self.interpret))
            # compact active lane rows into the dispatch width (pad rows
            # are inactive: null tables, zero budget)
            bt = np.full((w, self.max_blocks), NULL_BLOCK, np.int32)
            lengths = np.zeros(w, np.int32)
            remaining = np.zeros(w, np.int32)
            tok = np.zeros(w, np.int32)
            bt[:n_act] = self.block_tables[act]
            lengths[:n_act] = self.lengths[act]
            remaining[:n_act] = self.remaining[act]
            tok[:n_act] = self.last_tok[act]
            old_remaining = remaining.copy()

        rids = [self.lanes[i].req.rid for i in act] if tr.enabled else None
        with tr.span("decode_scan", track=self.track,
                     label=f"decode:{w}x{k_eff}", lanes=n_act, scan=k_eff,
                     rids=rids):
            self.pool, tok_o, lengths_o, remaining_o, toks = fn(
                self.params, self.pool, jnp.asarray(tok[:, None]),
                jnp.asarray(bt), jnp.asarray(lengths),
                jnp.asarray(remaining))
        self.decode_dispatches += 1
        self.lane_steps += w * k_eff
        self._active_frac_sum += n_act / w
        return {
            "act": act, "n_act": n_act, "k_eff": k_eff,
            "old_remaining": old_remaining,
            # lane identity per active row: a row only writes back if its
            # slot still holds the SAME lane (evict_latest can free a slot
            # — and admit_shipped can re-seat it — while the scan runs)
            "lanes": [self.lanes[i] for i in act],
            "tok_o": tok_o, "lengths_o": lengths_o,
            "remaining_o": remaining_o, "toks": toks,
        }

    def finish_dispatch(self, pending: Optional[dict],
                        now: float) -> List[Lane]:
        """Block on a ``dispatch_async`` record's device results, write back
        lane state and retire finished lanes."""
        if pending is None:
            return []
        act, n_act = pending["act"], pending["n_act"]
        k_eff = pending["k_eff"]
        old_remaining = pending["old_remaining"]
        tr = get_tracer()
        with tr.span("decode_readback", track=self.track):
            toks = np.asarray(pending["toks"])
            tok_o = np.asarray(pending["tok_o"])
            lengths_o = np.asarray(pending["lengths_o"])
            remaining_o = np.asarray(pending["remaining_o"])

        retired: List[Lane] = []
        with tr.span("retire", track=self.track):
            for row, i in enumerate(act):
                lane = pending["lanes"][row]
                if self.lanes[i] is not lane:
                    # evicted mid-flight (ship backpressure): its tokens
                    # are discarded — the lane re-executes from prefill,
                    # and any stale device writes to its reallocated blocks
                    # were overwritten by later-enqueued work
                    continue
                self.last_tok[i] = tok_o[row, 0]
                self.lengths[i] = lengths_o[row]
                self.remaining[i] = remaining_o[row]
                n_take = min(int(old_remaining[row]), k_eff)
                lane.out.extend(int(t) for t in toks[row, :n_take])
                self.decoded_tokens += n_take
                if self.remaining[i] == 0:
                    self._release(i, register=True)
                    retired.append(lane)
                    tr.instant("retire", track=self.track,
                               req=lane.req.rid)
        return retired

    # ------------------------------------------------------------- metrics
    def stats(self) -> dict:
        # occupancy = useful decode lane-steps / dispatched lane-steps: the
        # fraction of scan slots that produced a kept token.  Comparable to
        # the gang path's (tokens / padded-lanes x longest-request) figure —
        # the number in-flight joins + early retirement are meant to raise.
        occ = self.decoded_tokens / max(self.lane_steps, 1)
        act = self._active_frac_sum / max(self.decode_dispatches, 1)
        return {
            "join_waves": self.join_waves,
            "joined": self.joined,
            "prefill_chunks": self.prefill_chunks,
            "decode_dispatches": self.decode_dispatches,
            "decoded_tokens": self.decoded_tokens,
            "lane_steps": self.lane_steps,
            "active_lane_frac_sum": round(self._active_frac_sum, 6),
            "batch_occupancy": round(occ, 4),
            "mean_active_lanes": round(act, 4),
            "free_blocks": self.alloc.free_blocks,
            "used_blocks": self.alloc.used_blocks,
            "evictable_blocks": self.alloc.evictable_blocks,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_query_tokens": self.prefix_query_tokens,
            "prefix_hit_rate": round(
                self.prefix_hit_tokens / max(self.prefix_query_tokens, 1), 4),
            "prefill_wait_lane_s": self.prefill_wait_lane_s,
            "prefill_busy_lane_s": self.prefill_busy_lane_s,
            "cow_copies": self.cow_copies,
            "preemptions": self.preemptions,
            "spilled_blocks": self.spilled_blocks,
            "re_executions": self.re_executions,
            "recovered": self.recovered,
            "kv_block_bytes": self.kv_block_bytes,
            "kv_block_bytes_f32": self.kv_block_bytes_f32,
            # effective-capacity multiplier: KV blocks per byte vs f32
            "kv_capacity_x": round(
                self.kv_block_bytes_f32 / max(self.kv_block_bytes, 1), 4),
            **self.quant_telemetry,
            **{f"compile_{k}": v for k, v in self.compile_stats.items()},
        }
