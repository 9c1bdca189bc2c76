"""Pipeline execution for the paper's LAYER split.

The layer-wise split places a sequential chain of model fragments across
hosts; the fragment unit is the superblock stack that
``repro.models.transformer`` scans over.  Two execution substrates live here:

1. **GSPMD microbatch streaming** (``schedule="gspmd"``, the historical
   path): ``pipeline_param_specs`` (sharding.py) puts the stacked-superblock
   dim on the mesh 'model' axis and ``microbatch_loss`` streams M microbatches
   through the stack with an outer ``lax.scan``; GSPMD invents the
   stage-to-stage communication as a compiler side effect.

2. **The explicit stage-graph runtime** (``schedule="gpipe" | "1f1b"``):
   a static tick table (:class:`Schedule`) drives a ``shard_map`` program in
   which every mesh 'model' slice owns its contiguous superblock span as real
   local params (``stage_param_specs``) and activations/cotangents move
   between stages with explicit ``lax.ppermute`` — stage communication is a
   schedulable, measurable object.  ``"gpipe"`` is fill–drain (all forwards,
   then all backwards; peak of M in-flight microbatch activations);
   ``"1f1b"`` interleaves one-forward-one-backward in steady state, cutting
   peak in-flight activations to ~S.  In a single unconstrained flush both
   schedules share the makespan 2(M+S-1) and bubble (S-1)/(M+S-1); the 1f1b
   advantage is real at a fixed activation budget K, where GPipe must split
   into M/K fill–drain rounds and its bubble multiplies (the
   ``memory_budget`` knob models exactly this).  Backward is *manual*: each
   tick re-runs the stage forward under ``jax.vjp`` from the saved stage
   input (remat-style), so memory is set by the schedule's saved-slot count,
   not by autodiff residuals.

The same shard_map substrate executes **expert parallelism** end-to-end for
MoE configs (``ep_loss`` / ``ep_value_and_grad``): the mesh 'model' axis
carries experts instead of stages (the two uses are exclusive), and
``models.moe._moe_apply_ep`` exchanges token buffers with a pair of tiled
all-to-alls instead of gathering expert weights.

Numerics contract (tests/test_pipeline_schedules.py, scripts/smoke_dist.py):
dense-model loss is invariant to ``n_microbatches`` and matches the fsdp
runner to float-reduction noise on every schedule.  MoE capacity dispatch
happens per microbatch (and per data shard), so token dropping differs from
global dispatch — parity there is approximate by design unless the capacity
factor is raised so nothing drops (tolerance documented at the call sites).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as SH

SCHEDULES = ("gspmd", "gpipe", "1f1b")


def resolve_microbatches(batch_size: int, requested, n_stages: int) -> int:
    """Pick the microbatch count.  An explicit request must divide the batch;
    the default is the stage count (mesh 'model' size) clamped to a divisor
    of the batch so the schedule always tiles exactly."""
    if requested is not None:
        if batch_size % requested:
            raise ValueError(
                f"n_microbatches={requested} does not divide batch "
                f"size {batch_size}")
        return requested
    return math.gcd(batch_size, max(n_stages, 1)) or 1


def split_microbatches(batch, n_micro: int):
    """[B, ...] leaves -> [M, B/M, ...] (leading scan axis)."""
    def split(x):
        b = x.shape[0]
        return x.reshape((n_micro, b // n_micro) + x.shape[1:])
    return jax.tree.map(split, batch)


def microbatch_loss(model, params, batch, n_micro: int, *,
                    remat: bool = False, chunk: int = 512):
    """Mean per-token loss over M microbatches (gradient accumulation under
    grad).  M=1 degenerates to the plain full-batch loss."""
    if n_micro <= 1:
        return model.loss_chunked(params, batch, chunk=chunk, remat=remat)
    mbs = split_microbatches(batch, n_micro)

    def body(total, mb):
        loss = model.loss_chunked(params, mb, chunk=chunk, remat=remat)
        return total + loss, None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), mbs)
    return total / n_micro


# =========================================================== schedule tables
@dataclasses.dataclass(frozen=True)
class Schedule:
    """Static tick table driving the stage-graph executor.

    All tables are [ticks, n_stages] int32.  ``*_mb`` holds the microbatch
    index whose forward/backward stage ``s`` runs at tick ``t`` (-1: idle);
    the slot tables index the executor's fwd-arrival / saved-input /
    bwd-arrival ring buffers (the last slot of each buffer is a trash slot
    that absorbs masked SPMD garbage).  Built once in Python — the executor
    just streams the rows through a ``lax.scan``.
    """
    kind: str
    n_stages: int
    n_micro: int
    ticks: int
    f_mb: np.ndarray
    f_read: np.ndarray
    f_save: np.ndarray
    f_wslot: np.ndarray
    b_mb: np.ndarray
    b_slot: np.ndarray
    b_read: np.ndarray
    b_wslot: np.ndarray
    n_fwd_slots: int       # incl. trash
    n_saved_slots: int     # incl. trash
    n_bwd_slots: int       # incl. trash

    @property
    def n_ops(self) -> int:
        return int((self.f_mb >= 0).sum() + (self.b_mb >= 0).sum())

    @property
    def bubble_fraction(self) -> float:
        """Idle fraction of the tick grid: 1 - busy_slots / (ticks * stages)."""
        return 1.0 - self.n_ops / float(self.ticks * self.n_stages)

    @property
    def peak_saved_microbatches(self) -> int:
        """Max in-flight saved stage inputs (the schedule's activation-memory
        knob: M for gpipe, O(S) for 1f1b)."""
        return self.n_saved_slots - 1

    @property
    def n_transfers(self) -> int:
        """Scheduled (non-masked) stage-to-stage payload sends per step."""
        fwd = int((self.f_mb[:, : self.n_stages - 1] >= 0).sum())
        bwd = int((self.b_mb[:, 1:] >= 0).sum())
        return fwd + bwd


def _op_queues(kind: str, S: int, M: int, forward_only: bool,
               memory_budget: Optional[int]):
    if forward_only:
        return [[("F", m) for m in range(M)] for _ in range(S)]
    if kind == "gpipe":
        # Fill–drain.  A memory budget of K < M saved microbatches forces
        # GPipe into ceil(M/K) sequential fill–drain rounds (it must flush
        # before admitting more microbatches than it can save) — the regime
        # where 1f1b's bubble advantage is real rather than nominal.
        K = M if memory_budget is None else max(1, min(memory_budget, M))
        q = []
        for lo in range(0, M, K):
            mbs = range(lo, min(lo + K, M))
            q += [("F", m) for m in mbs] + [("B", m) for m in reversed(mbs)]
        return [list(q) for _ in range(S)]
    if kind == "1f1b":
        queues = []
        for i in range(S):
            warm = min(M, S - i)
            q = [("F", m) for m in range(warm)]
            nf, nb = warm, 0
            while nb < M:
                q.append(("B", nb))
                nb += 1
                if nf < M:
                    q.append(("F", nf))
                    nf += 1
            queues.append(q)
        return queues
    raise ValueError(f"unknown schedule {kind!r}; expected one of {SCHEDULES}")


def _simulate(queues, S: int):
    """Greedy list-scheduling of the per-stage op queues under the transfer
    constraints (an activation/cotangent sent at the end of tick t is
    consumable from tick t+1).  Returns (events, t_F, t_B) where events[t][s]
    is ('F'|'B', mb) or None."""
    t_F: Dict[Tuple[int, int], int] = {}
    t_B: Dict[Tuple[int, int], int] = {}
    ptr = [0] * S
    total = sum(len(q) for q in queues)
    done, t, events = 0, 0, []
    INF = 1 << 30
    while done < total:
        if t > 16 * (total + S):
            raise RuntimeError(f"schedule deadlock: {queues}")
        row = [None] * S
        for i in range(S):
            if ptr[i] >= len(queues[i]):
                continue
            op, m = queues[i][ptr[i]]
            if op == "F":
                ready = i == 0 or t_F.get((i - 1, m), INF) < t
            else:
                ready = t_F.get((i, m), INF) < t and (
                    i == S - 1 or t_B.get((i + 1, m), INF) < t)
            if ready:
                row[i] = (op, m)
        for i, r in enumerate(row):
            if r is None:
                continue
            op, m = r
            (t_F if op == "F" else t_B)[(i, m)] = t
            ptr[i] += 1
            done += 1
        events.append(row)
        t += 1
    return events, t_F, t_B


def _alloc_slots(intervals):
    """Greedy interval-partitioning.  ``intervals``: [(write_tick, last_read
    _tick, key)]; a slot written at tick w is reusable once its last read
    tick r satisfies w_new >= r (the executor reads all buffers before it
    writes).  Returns ({key: slot}, n_slots)."""
    assign, slot_free_at = {}, []
    for w, r, key in sorted(intervals):
        for j, free_at in enumerate(slot_free_at):
            if free_at <= w:
                assign[key] = j
                slot_free_at[j] = r
                break
        else:
            assign[key] = len(slot_free_at)
            slot_free_at.append(r)
    return assign, len(slot_free_at)


def build_schedule(kind: str, n_stages: int, n_micro: int, *,
                   forward_only: bool = False,
                   memory_budget: Optional[int] = None) -> Schedule:
    """Build the static tick table for one (schedule, S, M) triple.

    ``memory_budget`` (gpipe only) caps the saved in-flight microbatches,
    splitting the flush into fill–drain rounds.  1f1b's peak is structurally
    ~S and ignores the knob.  With both schedules at the same budget K=S,
    1f1b's bubble fraction (S-1)/(M+S-1) beats gpipe's round-multiplied
    (M/K)(S-1) / ((M/K)(S-1) + M); unbounded gpipe matches 1f1b's bubble but
    holds M saved microbatches instead of ~S.
    """
    S, M = n_stages, n_micro
    events, t_F, t_B = _simulate(
        _op_queues(kind, S, M, forward_only, memory_budget), S)
    T = len(events)

    # ---- slot allocation (per stage; buffers are uniform across devices, so
    # the executor sizes them at the max over stages, plus one trash slot).
    fwd_iv = [[] for _ in range(S)]    # (i, m): sent end of t_F(i-1,m), read at t_F(i,m)
    sav_iv = [[] for _ in range(S)]    # (i, m): saved at t_F(i,m), read at t_B(i,m)
    bwd_iv = [[] for _ in range(S)]    # (i, m): sent end of t_B(i+1,m), read at t_B(i,m)
    for (i, m), t in t_F.items():
        if i > 0:
            fwd_iv[i].append((t_F[(i - 1, m)], t, (i, m)))
        if not forward_only:
            sav_iv[i].append((t, t_B[(i, m)], (i, m)))
    for (i, m), t in t_B.items():
        if i < S - 1:
            bwd_iv[i].append((t_B[(i + 1, m)], t, (i, m)))
    fwd_slot, sav_slot, bwd_slot = {}, {}, {}
    n_fwd = n_sav = n_bwd = 0
    for i in range(S):
        a, n = _alloc_slots(fwd_iv[i])
        fwd_slot.update(a)
        n_fwd = max(n_fwd, n)
        a, n = _alloc_slots(sav_iv[i])
        sav_slot.update(a)
        n_sav = max(n_sav, n)
        a, n = _alloc_slots(bwd_iv[i])
        bwd_slot.update(a)
        n_bwd = max(n_bwd, n)
    trash_f, trash_s, trash_b = n_fwd, n_sav, n_bwd

    # ---- tables
    f_mb = np.full((T, S), -1, np.int32)
    b_mb = np.full((T, S), -1, np.int32)
    f_read = np.full((T, S), trash_f, np.int32)
    f_save = np.full((T, S), trash_s, np.int32)
    f_wslot = np.full((T, S), trash_f, np.int32)
    b_slot = np.full((T, S), trash_s, np.int32)
    b_read = np.full((T, S), trash_b, np.int32)
    b_wslot = np.full((T, S), trash_b, np.int32)
    for t, row in enumerate(events):
        for i, r in enumerate(row):
            if r is None:
                continue
            op, m = r
            if op == "F":
                f_mb[t, i] = m
                if i > 0:
                    f_read[t, i] = fwd_slot[(i, m)]
                if not forward_only:
                    f_save[t, i] = sav_slot[(i, m)]
                if i + 1 < S:       # receiver's write slot for this send
                    f_wslot[t, i + 1] = fwd_slot[(i + 1, m)]
            else:
                b_mb[t, i] = m
                b_slot[t, i] = sav_slot[(i, m)]
                if i < S - 1:
                    b_read[t, i] = bwd_slot[(i, m)]
                if i - 1 >= 0:
                    b_wslot[t, i - 1] = bwd_slot[(i - 1, m)]
    return Schedule(kind=kind, n_stages=S, n_micro=M, ticks=T,
                    f_mb=f_mb, f_read=f_read, f_save=f_save, f_wslot=f_wslot,
                    b_mb=b_mb, b_slot=b_slot, b_read=b_read, b_wslot=b_wslot,
                    n_fwd_slots=n_fwd + 1, n_saved_slots=n_sav + 1,
                    n_bwd_slots=n_bwd + 1)


# ======================================================= stage-graph runtime
def _mesh_sizes(mesh) -> dict:
    return dict(mesh.shape)


def _has_model_axis(spec: P) -> bool:
    for e in spec:
        axes = e if isinstance(e, tuple) else (e,)
        if "model" in axes:
            return True
    return False


def _make_tick_core(model, remat: bool):
    """One stage's op as a *purely local* function (no collectives — the
    executor owns all communication), uniform across devices: embed is
    computed everywhere but only selected at stage 0, the head everywhere but
    only consumed (via the loss cotangent) at the last stage; ``jnp.where``
    on the stage index routes both values and, under ``jax.vjp``, their
    cotangents.  Payloads carry the activations plus the running MoE aux
    loss."""
    def tick_core(params, tokens_mb, labels_mb, recv, col):
        x_emb = model.stage_embed(params, tokens_mb)
        x_in = jnp.where(col == 0, x_emb, recv["x"])
        aux_in = jnp.where(col == 0, 0.0, recv["aux"])
        positions = jnp.arange(tokens_mb.shape[1])[None, :]
        y, aux_local = model.stage_apply(params["blocks"], x_in,
                                         positions=positions, remat=remat)
        aux_out = aux_in + aux_local
        loss_m = model.stage_head_loss(params, y, labels_mb) + 0.01 * aux_out
        return {"x": y, "aux": aux_out}, loss_m

    return tick_core


def _stage_setup(model, params, batch, mesh, n_micro: int):
    """Shared validation + microbatch reshape for the stage executors."""
    cfg = model.cfg
    if not getattr(model, "supports_stage_split", False):
        raise ValueError(
            f"{cfg.name}: the explicit stage-graph schedules support plain "
            "decoder-only stacks (no enc-dec / modality frontends); use "
            'schedule="gspmd"')
    sizes = _mesh_sizes(mesh)
    S = sizes.get("model", 1)
    n_data = sizes.get("data", 1)
    if cfg.n_superblocks % max(S, 1):
        raise ValueError(
            f"{cfg.name}: n_superblocks={cfg.n_superblocks} not divisible by "
            f"mesh 'model' size {S}")
    tokens, labels = batch["tokens"], batch["labels"]
    B, s = tokens.shape
    if B % n_micro or (B // n_micro) % n_data:
        raise ValueError(
            f"batch {B} must split into n_microbatches={n_micro} x "
            f"data axis {n_data}")
    mt = tokens.reshape(n_micro, B // n_micro, s)
    ml = labels.reshape(n_micro, B // n_micro, s)
    return S, n_data, mt, ml


def _payload_zero(cfg, b_local: int, seq: int):
    return {"x": jnp.zeros((b_local, seq, cfg.d_model), jnp.dtype(cfg.dtype)),
            "aux": jnp.zeros((), jnp.float32)}


def payload_bytes(cfg, b_local: int, seq: int) -> int:
    return b_local * seq * cfg.d_model * jnp.dtype(cfg.dtype).itemsize + 4


def _stack_zero(payload, n: int):
    return jax.tree.map(
        lambda x: jnp.zeros((n,) + x.shape, x.dtype), payload)


def stage_graph_loss(model, params, batch, mesh, *, schedule: str = "gpipe",
                     n_micro: int = 1, remat: bool = False):
    """Forward-only stage-graph loss: fill the pipeline with M microbatches
    under explicit ppermute transfers and psum the last stage's masked
    per-microbatch mean losses.  Loss value is schedule-independent."""
    S, n_data, mt, ml = _stage_setup(model, params, batch, mesh, n_micro)
    sched = build_schedule(schedule, S, n_micro, forward_only=True)
    cfg = model.cfg
    b_local = mt.shape[1] // n_data
    seq = mt.shape[2]
    p_specs = SH.stage_param_specs(params, mesh)
    tick_core = _make_tick_core(model, remat)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    tables = (jnp.asarray(sched.f_mb), jnp.asarray(sched.f_read),
              jnp.asarray(sched.f_wslot))

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(p_specs, P(None, "data"), P(None, "data")),
             out_specs=P(), check_vma=False)
    def run(params, mt, ml):
        col = jax.lax.axis_index("model")
        fwd_buf = _stack_zero(_payload_zero(cfg, b_local, seq),
                              sched.n_fwd_slots)

        def body(carry, xs):
            fwd_buf, loss_acc = carry
            f_mb_r, f_read_r, f_w_r = xs
            f_m, f_rd, f_w = f_mb_r[col], f_read_r[col], f_w_r[col]
            recv = jax.tree.map(lambda b: b[f_rd], fwd_buf)
            tok = mt[jnp.clip(f_m, 0)]
            lab = ml[jnp.clip(f_m, 0)]
            payload, loss_m = tick_core(params, tok, lab, recv, col)
            take = (col == S - 1) & (f_m >= 0)
            loss_acc = loss_acc + jnp.where(take, loss_m, 0.0) / n_micro
            arr = jax.lax.ppermute(payload, "model", fwd_perm)
            fwd_buf = jax.tree.map(lambda b, v: b.at[f_w].set(v),
                                   fwd_buf, arr)
            return (fwd_buf, loss_acc), None

        (_, loss_acc), _ = jax.lax.scan(
            body, (fwd_buf, jnp.zeros((), jnp.float32)), tables)
        loss = jax.lax.psum(loss_acc, "model")
        return jax.lax.pmean(loss, "data")

    return run(params, mt, ml)


def stage_graph_value_and_grad(model, params, batch, mesh, *,
                               schedule: str = "gpipe", n_micro: int = 1,
                               remat: bool = False,
                               memory_budget: Optional[int] = None):
    """(loss, grads) under an explicit pipeline schedule.

    Backward is manual remat-style 1-tick vjp: each scheduled B op re-runs the
    stage forward from the *saved stage input* and pulls the arriving (or, at
    the last stage, the loss) cotangent back through it; the resulting input
    cotangent is ppermuted to the upstream stage.  Masked (SPMD-garbage) ops
    contribute exactly zero because their cotangents are zero and pullbacks
    are linear.  Grads: pmean over 'data' everywhere; leaves replicated over
    'model' (embed / final norm — touched only at the first/last stage) are
    additionally psum'd over 'model'.
    """
    S, n_data, mt, ml = _stage_setup(model, params, batch, mesh, n_micro)
    sched = build_schedule(schedule, S, n_micro, memory_budget=memory_budget)
    cfg = model.cfg
    b_local = mt.shape[1] // n_data
    seq = mt.shape[2]
    p_specs = SH.stage_param_specs(params, mesh)
    tick_core = _make_tick_core(model, remat)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]
    tables = tuple(jnp.asarray(a) for a in (
        sched.f_mb, sched.f_read, sched.f_save, sched.f_wslot,
        sched.b_mb, sched.b_slot, sched.b_read, sched.b_wslot))

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(p_specs, P(None, "data"), P(None, "data")),
             out_specs=(P(), p_specs), check_vma=False)
    def run(params, mt, ml):
        col = jax.lax.axis_index("model")
        zero_payload = _payload_zero(cfg, b_local, seq)
        fwd_buf = _stack_zero(zero_payload, sched.n_fwd_slots)
        sav_buf = _stack_zero(zero_payload, sched.n_saved_slots)
        bwd_buf = _stack_zero(zero_payload, sched.n_bwd_slots)
        grad_acc = jax.tree.map(jnp.zeros_like, params)
        is_last = col == S - 1

        def body(carry, xs):
            fwd_buf, sav_buf, bwd_buf, loss_acc, grad_acc = carry
            f_mb_r, f_read_r, f_save_r, f_w_r, \
                b_mb_r, b_slot_r, b_read_r, b_w_r = xs
            f_m, f_rd, f_sv, f_w = (f_mb_r[col], f_read_r[col],
                                    f_save_r[col], f_w_r[col])
            b_m, b_sl, b_rd, b_w = (b_mb_r[col], b_slot_r[col],
                                    b_read_r[col], b_w_r[col])
            # ---- reads (all before any write: slots reuse at read tick)
            recv_f = jax.tree.map(lambda b: b[f_rd], fwd_buf)
            saved = jax.tree.map(lambda b: b[b_sl], sav_buf)
            ct_x = bwd_buf["x"][b_rd]
            ct_aux = bwd_buf["aux"][b_rd]
            # ---- forward op
            tok_f, lab_f = mt[jnp.clip(f_m, 0)], ml[jnp.clip(f_m, 0)]
            payload, loss_m = tick_core(params, tok_f, lab_f, recv_f, col)
            take = is_last & (f_m >= 0)
            loss_acc = loss_acc + jnp.where(take, loss_m, 0.0) / n_micro
            # ---- backward op (remat vjp from the saved stage input)
            tok_b, lab_b = mt[jnp.clip(b_m, 0)], ml[jnp.clip(b_m, 0)]
            b_valid = b_m >= 0
            _, pull = jax.vjp(
                lambda p, rv: tick_core(p, tok_b, lab_b, rv, col),
                params, saved)
            mid = b_valid & (~is_last)
            ct_payload = {
                "x": jnp.where(mid, ct_x, jnp.zeros_like(ct_x)),
                "aux": jnp.where(mid, ct_aux, 0.0)}
            ct_loss = jnp.where(b_valid & is_last,
                                jnp.float32(1.0 / n_micro), 0.0)
            d_params, d_recv = pull((ct_payload, ct_loss))
            grad_acc = jax.tree.map(jnp.add, grad_acc, d_params)
            # ---- explicit stage-to-stage transfers
            f_arr = jax.lax.ppermute(payload, "model", fwd_perm)
            b_arr = jax.lax.ppermute(d_recv, "model", bwd_perm)
            fwd_buf = jax.tree.map(lambda b, v: b.at[f_w].set(v),
                                   fwd_buf, f_arr)
            bwd_buf = jax.tree.map(lambda b, v: b.at[b_w].set(v),
                                   bwd_buf, b_arr)
            sav_buf = jax.tree.map(lambda b, v: b.at[f_sv].set(v),
                                   sav_buf, recv_f)
            return (fwd_buf, sav_buf, bwd_buf, loss_acc, grad_acc), None

        init = (fwd_buf, sav_buf, bwd_buf, jnp.zeros((), jnp.float32),
                grad_acc)
        (_, _, _, loss_acc, grad_acc), _ = jax.lax.scan(body, init, tables)
        loss = jax.lax.pmean(jax.lax.psum(loss_acc, "model"), "data")

        def reduce_grad(g, spec):
            g = jax.lax.pmean(g, "data")
            if not _has_model_axis(spec):
                g = jax.lax.psum(g, "model")
            return g

        grads = jax.tree.map(reduce_grad, grad_acc, p_specs)
        return loss, grads

    return run(params, mt, ml)


# ==================================================== expert-parallel runtime
def _ep_specs(model, params, batch, mesh, n_micro: int):
    """Specs + divisibility validation for the EP substrate: the batch is
    sharded over 'data' and the *local* shard is what splits into
    microbatches inside shard_map."""
    n_data = _mesh_sizes(mesh).get("data", 1)
    B = batch["tokens"].shape[0]
    if B % n_data or (B // n_data) % n_micro:
        raise ValueError(
            f"expert-parallel batch {B} must split into data axis {n_data} "
            f"x n_microbatches={n_micro}")
    p_specs = SH.stage_param_specs(params, mesh, expert_parallel=True)
    return p_specs, SH.batch_specs(model.cfg, mesh, batch)


def ep_loss(model, params, batch, mesh, *, n_micro: int = 1,
            remat: bool = False):
    """Expert-parallel loss on the shard_map substrate: expert weights live
    sharded over the mesh 'model' axis and ``models.moe._moe_apply_ep``'s
    all-to-alls exchange token buffers end-to-end (``model`` must be built
    with ``expert_parallel_axis="model"``).  Non-expert compute is replicated
    over 'model'; the batch is sharded over 'data'."""
    p_specs, b_specs = _ep_specs(model, params, batch, mesh, n_micro)

    @partial(jax.shard_map, mesh=mesh, in_specs=(p_specs, b_specs),
             out_specs=P(), check_vma=False)
    def run(params, batch):
        loss = microbatch_loss(model, params, batch, n_micro, remat=remat)
        return jax.lax.pmean(loss, "data")

    return run(params, batch)


def ep_value_and_grad(model, params, batch, mesh, *, n_micro: int = 1,
                      remat: bool = False):
    """(loss, grads) for the expert-parallel substrate.

    Each 'model' replica computes the full (replicated) loss on its 'data'
    shard; expert-weight cotangents returning through the all-to-all transpose
    therefore accumulate one full contribution *per replica* and are divided
    by the axis size, while replicated leaves already hold the exact local
    grad (their loss path is entirely on-device).  Everything is pmean'd over
    'data'.  Verified against the layout-level (dense-dispatch) path in
    tests/test_pipeline_schedules.py."""
    p_specs, b_specs = _ep_specs(model, params, batch, mesh, n_micro)
    n_model = _mesh_sizes(mesh).get("model", 1)

    @partial(jax.shard_map, mesh=mesh, in_specs=(p_specs, b_specs),
             out_specs=(P(), p_specs), check_vma=False)
    def run(params, batch):
        loss, grads = jax.value_and_grad(
            lambda p: microbatch_loss(model, p, batch, n_micro,
                                      remat=remat))(params)

        def reduce_grad(g, spec):
            if _has_model_axis(spec):
                g = g / n_model
            return jax.lax.pmean(g, "data")

        grads = jax.tree.map(reduce_grad, grads, p_specs)
        return jax.lax.pmean(loss, "data"), grads

    return run(params, batch)
