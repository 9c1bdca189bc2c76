"""Paged single-token decode attention — Pallas TPU kernel.

The continuous-batching decode path (``repro.decode``) keeps the KV cache as
a pool of fixed-size physical blocks (pages) per layer, stacked over the
layers: each K/V leaf is ``[N_layers, P, bs, K, hd]`` (int8 pools add
per-slot ``[N_layers, P, bs, K]`` scale leaves).  A per-sequence block
table maps logical block j to its physical page.

The kernel reads that stacked pool where it lies, in HBM, and writes the
step's new token into it in place:

* the K/V leaves are ``pl.ANY`` operands aliased to the outputs, so the
  layer scan hands the whole stack to the call with the layer index and
  nothing is sliced, transposed or copied per layer;
* per lane it DMAs only the pages below the lane's fill level, each page
  one contiguous copy holding every kv head, several pages per compute step
  and double-buffered against the compute;
* the lane's write page (its last, ``wb``) takes the new token's K/V at
  slot ``wo`` in VMEM, so the attention sees it, and is DMA'd back to
  ``[layer, wb]`` whole; an inactive lane, whose write slot is the reserved
  null block and none of its pages, writes nothing;
* each step attends a head group's queries to the group's keys of every
  page at once on the MXU; a mask keeps the block diagonal (query head k
  against key head k) and the positions below the fill level, and the same
  f32 online softmax folds the steps.  Queries, keys, values and softmax
  weights enter both matmuls in f32, as in the dense reference.

A slot's K/V is laid out as the chip's DMA can slice it
(:func:`slot_shape`, the view the decode step carries its pool in): with
``hd < 128`` a row of 128 lanes holds ``128 / hd`` heads side by side (a
minor dimension of 64 cannot be sliced), and a slot of fewer rows than the
dtype packs into a sublane is one row of all its heads.  The query rows
then carry each head's query in its own lane segment, zeros elsewhere, so
one matmul over a row's lanes scores every head in it and the mask picks
each query's own.

Pages per step and the head group come from the shapes alone: a step moves
about ``GROUP_BYTES`` of each leaf, and a head group is the pool dtype's
sublane tile of slot rows (or all of them when fewer).  Tables of different
lanes may alias the same page (prefix sharing): reads go through the table,
and the only page a lane writes is its own write block, never a shared one.

int8 pools dequantize in VMEM; their per-slot scales (a 4-byte number per
kv head) take an XLA scatter into the stacked scale leaf and a per-lane
gather beside the call, which is far smaller than the codes.

Under ``jax.vmap`` (the SEMANTIC arm's branches) the branch axis folds into
the kernel's grid and the pool's leading index (``custom_vmap``), so the
branches' pools go in and out of one call as they lie, too.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: bytes of one K/V leaf that one compute step brings in (pages per step)
GROUP_BYTES = 256 * 1024

LANES = 128


def slot_shape(kh: int, hd: int, dtype) -> tuple:
    """The shape of one token slot's ``kh`` heads of width ``hd`` in the
    kernel's view: ``(rows, 128)`` when ``hd`` divides 128, else the
    natural ``(kh, hd)``; and ``(kh * hd,)``, one row, when that leaves
    fewer rows than ``dtype`` packs into a 32-bit sublane (2 for bf16, 4
    for int8), which a DMA cannot slice."""
    rows, lanes = kh, hd
    if hd % LANES and LANES % hd == 0 and (kh * hd) % LANES == 0:
        rows, lanes = kh * hd // LANES, LANES
    if lanes % LANES == 0 and rows < 4 // jnp.dtype(dtype).itemsize:
        return (rows * lanes,)
    return rows, lanes


def pages_per_step(page_bytes: int, max_pages: int) -> int:
    """Pages one compute step DMAs: a power of two near ``GROUP_BYTES``
    of a leaf, at most the table's width."""
    n = max(1, GROUP_BYTES // page_bytes)
    return min(1 << (n.bit_length() - 1), max_pages)


def head_group(rows: int, dtype) -> int:
    """Slot rows attended per MXU call: the dtype's sublane tile (8 rows of
    32 bits, so 16 bf16 or 32 int8 rows), or all ``rows`` when fewer or not
    a multiple of it.  A call scores each query against every head of its
    rows, so smaller groups are less work, and a tile-aligned slice of the
    page needs no relayout."""
    tile = 32 // jnp.dtype(dtype).itemsize
    return tile if rows > tile and rows % tile == 0 else rows


def _kernel(layer_ref, len_ref, bt_ref, wb_ref, wo_ref, q_ref, *refs,
            n_pages, group, rep, hd, scale, softcap, quantized):
    # scalar prefetch (SMEM): layer [1], lengths [B], tables [B, NB], write
    # slots wb/wo [B].  q_ref: [H, lanes], each head's query in its lane
    # segment.  Then: with int8, the lane's scales k/v [NB*bs, K] (heads in
    # segment-major order); the lane's new K/V slot (``slot``: [R, lanes]
    # or [lanes]); the stacked K/V leaves (ANY [Bb, N, P, bs, *slot]);
    # o_ref [H, lanes]; the aliased K/V outputs; K/V page buffers
    # [2, n, bs, *slot]; the read semaphores [2] and the write-back one.
    keys = ("k", "v")
    scales = dict(zip(keys, refs[:2])) if quantized else {}
    refs = refs[2:] if quantized else refs
    c, b = pl.program_id(0), pl.program_id(1)
    new = dict(zip(keys, refs[0:2]))
    pool = dict(zip(keys, refs[2:4]))
    o_ref = refs[4]
    pool_out = dict(zip(keys, refs[5:7]))
    buf = dict(zip(keys, refs[7:9]))
    sems, wsem = refs[9:]

    layer = layer_ref[0]
    length = len_ref[b]
    wblk, woff = wb_ref[b], wo_ref[b]
    bs, *slot_rows, lanes = buf["k"].shape[2:]
    r = slot_rows[0] if slot_rows else 1
    f = lanes // hd                               # heads side by side
    n_valid = pl.cdiv(length, bs)                 # pages holding tokens
    n_steps = pl.cdiv(n_valid, n_pages)

    def each_page(step, fn):
        """``fn(j, page)`` for each valid page j of compute step ``step``
        (a loop, so the traced kernel stays small at any pages per step)."""
        first = step * n_pages

        def one(j, _):
            fn(j, bt_ref[b, first + j])
            return 0

        jax.lax.fori_loop(0, jnp.minimum(n_pages, n_valid - first), one, 0)

    def dmas(slot, j, page):
        return [pltpu.make_async_copy(pool[k].at[c, layer, page],
                                      buf[k].at[slot, j], sems.at[slot])
                for k in keys]

    def writeback(slot, j, k):
        return pltpu.make_async_copy(buf[k].at[slot, j],
                                     pool_out[k].at[c, layer, wblk], wsem)

    def start(step, slot):
        def go(j, page):
            for d in dmas(slot, j, page):
                d.start()
        each_page(step, go)

    @pl.when((c == 0) & (b == 0))
    def _():
        # slots past a lane's last page are never loaded: keep them finite
        # (zeros, then pool pages) so masked scores times them stay zero
        for k in keys:
            buf[k][...] = jnp.zeros(buf[k].shape, buf[k].dtype)

    start(0, 0)

    t = n_pages * bs                              # token slots per step
    rows, cols = group * f * rep, t * group
    # key column c is token c // group, slot row c % group of the group;
    # query row q holds head q // rep, which lies in slot row q // (rep * f)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    qrow = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    col_row = col % group
    diag = (qrow >= col_row * rep * f) & (qrow < (col_row + 1) * rep * f)
    col_tok = col // group
    if quantized and f > 1:
        seg = jax.lax.broadcasted_iota(jnp.int32, (t, group, lanes), 2) // hd

    def head_rows(key, slot, g, step):
        """Slot rows [g*group, (g+1)*group) of every page in the slot as
        f32 MXU rows [t * group, lanes], int8 codes times their scales."""
        x = buf[key][slot]
        if slot_rows:
            x = x[:, :, g * group:(g + 1) * group, :]
        x = x.reshape(t, group, lanes).astype(jnp.float32)
        if quantized:
            sc = scales[key][pl.ds(pl.multiple_of(step * t, t), t), :]
            mult = None
            for s in range(f):                    # segment s: heads j*f + s
                lo = s * r + g * group
                m = sc[:, lo:lo + group][:, :, None]
                mult = m if mult is None else jnp.where(seg == s, m, mult)
            x = x * mult
        return x.reshape(cols, lanes)

    q = q_ref[...].astype(jnp.float32)
    n_groups = r // group

    def body(step, carry):
        slot = step % 2

        start(step + 1, 1 - slot)

        def land(j, page):
            for d in dmas(slot, j, page):
                d.wait()

            # the lane's write page (its last) takes the new token's row
            # and goes back to the pool whole
            @pl.when(page == wblk)
            def _():
                for k in keys:
                    if slot_rows:
                        buf[k][slot, j, woff] = new[k][...]
                    else:               # one-row slots: the page's row woff
                        wide = (jnp.int32 if jnp.issubdtype(
                            buf[k].dtype, jnp.integer) else jnp.float32)
                        cur = buf[k][slot, j].astype(wide)
                        hit = jax.lax.broadcasted_iota(
                            jnp.int32, cur.shape, 0) == woff
                        buf[k][slot, j] = jnp.where(
                            hit, new[k][...].astype(wide),
                            cur).astype(buf[k].dtype)
                    writeback(slot, j, k).start()

        each_page(step, land)

        valid = diag & (step * t + col_tok < length)
        out = []
        for g in range(n_groups):
            acc, m_prev, l_prev = carry[g]
            qg = q[g * rows:(g + 1) * rows]
            s = jax.lax.dot_general(qg, head_rows("k", slot, g, step),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(valid, s, NEG_INF)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_cur)
            alpha = jnp.exp(m_prev - m_cur)
            l_cur = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            pv = jnp.dot(p, head_rows("v", slot, g, step),
                         preferred_element_type=jnp.float32)
            out.append((acc * alpha + pv, m_cur, l_cur))
        return tuple(out)

    init = tuple((jnp.zeros((rows, lanes), jnp.float32),
                  jnp.full((rows, 1), NEG_INF, jnp.float32),
                  jnp.zeros((rows, 1), jnp.float32))
                 for _ in range(n_groups))
    res = jax.lax.fori_loop(0, n_steps, body, init)
    for g, (acc, _, l) in enumerate(res):
        o_ref[g * rows:(g + 1) * rows, :] = (
            acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
    # an active lane's write block is its last page; an inactive lane's
    # (the null block) is none of its pages, and it writes nothing
    last = bt_ref[b, jnp.maximum(n_valid - 1, 0)]

    @pl.when((n_valid > 0) & (last == wblk))
    def _():
        for k in keys:
            writeback(0, 0, k).wait()


def _call(q, new, pool, layer, block_tables, lengths, wb, wo, *, softcap,
          interpret):
    """The kernel over a leading branch axis: q [Bb, B, H, hd]; new rows
    [Bb, B, K, hd] / [Bb, B, K]; pool leaves [Bb, N, P, bs, R, lanes] /
    [Bb, N, P, bs, K].  Returns (out [Bb, B, H, hd], pool)."""
    bb, b, h, hd = q.shape
    bs, *slot = pool["k"].shape[3:]
    r, lanes = slot if len(slot) == 2 else (1, slot[0])
    kh = r * lanes // hd
    f = lanes // hd
    rep = h // kh
    assert rep * kh == h and f * hd == lanes
    assert tuple(slot) == slot_shape(kh, hd, pool["k"].dtype), (
        "the pool's K/V leaves must be in the kernel's slot view", slot)
    nb = block_tables.shape[1]
    dtype = pool["k"].dtype
    quantized = "k_scale" in pool
    n_pages = pages_per_step(bs * r * lanes * dtype.itemsize, nb)
    group = head_group(r, dtype)
    # each head's query in its lane segment of a slot row
    seg = jax.nn.one_hot((jnp.arange(h) // rep) % f, f, dtype=q.dtype)
    q2 = q if f == 1 else (q[..., None, :] * seg[:, :, None]).reshape(
        bb, b, h, lanes)

    def lane_spec(shape):
        return pl.BlockSpec((None, None) + shape,
                            lambda c, i, *_: (c, i) + (0,) * len(shape))

    pool = dict(pool)
    extra, extra_specs = [], []
    if quantized:
        for key in ("k_scale", "v_scale"):
            leaf = pool[key].at[:, layer, wb, wo].set(
                new[key].astype(pool[key].dtype))
            pool[key] = leaf
            # per lane [NB*bs, K], heads regrouped segment-major (s, row)
            sc = leaf[:, layer, block_tables].reshape(bb, b, nb * bs, r, f)
            extra.append(sc.swapaxes(-1, -2).reshape(bb, b, nb * bs, kh))
            extra_specs.append(lane_spec((nb * bs, kh)))
    # the new slot as a 2-D block (a one-row slot as [1, lanes])
    row_shape = tuple(slot) if len(slot) == 2 else (1, lanes)
    news = [new[k].astype(dtype).reshape((bb, b) + row_shape)
            for k in ("k", "v")]
    news_specs = [lane_spec(row_shape)] * 2
    leaves = [pool[k] for k in ("k", "v")]
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    n_scalar = 5
    n_in = n_scalar + 1 + len(extra) + 2          # index of the K leaf
    kernel = functools.partial(
        _kernel, n_pages=n_pages, group=group, rep=rep, hd=hd,
        scale=1.0 / math.sqrt(hd), softcap=softcap,
        quantized=quantized)
    out, k_leaf, v_leaf = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_scalar,
            grid=(bb, b),
            in_specs=[lane_spec((h, lanes))] + extra_specs + news_specs
            + [any_spec] * 2,
            out_specs=[lane_spec((h, lanes))] + [any_spec] * 2,
            scratch_shapes=[pltpu.VMEM((2, n_pages, bs, *slot), dtype)] * 2
            + [pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=[jax.ShapeDtypeStruct(q2.shape, q.dtype)]
        + [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in leaves],
        input_output_aliases={n_in: 1, n_in + 1: 2},
        interpret=interpret,
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      lengths.astype(jnp.int32), block_tables.astype(jnp.int32),
      wb.astype(jnp.int32), wo.astype(jnp.int32), q2, *extra, *news, *leaves)
    pool["k"], pool["v"] = k_leaf, v_leaf
    if f > 1:
        out = jnp.einsum("cbhfd,hf->cbhd", out.reshape(bb, b, h, f, hd), seg)
    return out, pool


@functools.lru_cache(maxsize=None)
def _entry(softcap: float, interpret: bool):
    call = functools.partial(_call, softcap=softcap, interpret=interpret)

    @jax.custom_batching.custom_vmap
    def attend(q, new, pool, layer, block_tables, lengths, wb, wo):
        lead = lambda x: x[None]
        out, pool = call(lead(q), jax.tree.map(lead, new),
                         jax.tree.map(lead, pool), layer, block_tables,
                         lengths, wb, wo)
        return out[0], jax.tree.map(lambda x: x[0], pool)

    @attend.def_vmap
    def _branches(axis_size, in_batched, q, new, pool, layer, block_tables,
                  lengths, wb, wo):
        # the vmapped axis becomes the kernel's branch axis; tables,
        # lengths and write slots are shared by every branch
        if any(jax.tree.leaves(in_batched[3:])):
            raise NotImplementedError(
                "paged_decode_attention batches over pool branches only")
        fill = lambda x, batched: x if batched else jnp.broadcast_to(
            x, (axis_size,) + x.shape)
        q, new, pool = (jax.tree.map(fill, x, bt)
                        for x, bt in zip((q, new, pool), in_batched[:3]))
        out, pool = call(q, new, pool, layer, block_tables, lengths, wb, wo)
        return (out, pool), (True, jax.tree.map(lambda _: True, pool))

    return attend


def paged_decode_attention(q, new, pool, layer, block_tables, lengths, wb,
                           wo, *, softcap: float = 0.0,
                           interpret: bool = False):
    """One decode token per lane against the stacked paged pool, the
    token's K/V written into it in place.

    q: [B, H, hd]; pool: the arm's stacked leaves, ``"k"``/``"v"``
    [N, P, bs, *slot_shape(K, hd, dtype)] holding a slot's K heads of width
    hd in the view a DMA can slice, and for int8 pools ``"k_scale"``/``"v_scale"`` [N, P, bs, K]; new: the
    step's K/V [B, K, hd] (and scales [B, K]) under the same keys, written
    at ``[layer, wb, wo]`` where ``wb`` is the lane's last page (an
    inactive lane's null-block slot keeps its K/V; scales, written by XLA,
    land there too); layer: scalar index
    into N; block_tables: [B, NB] int32; lengths: [B] valid tokens per
    lane, the new one included.  Returns
    (out [B, H, hd], pool): the K/V leaves are the inputs updated in place
    (aliased), so a caller that donates or carries them holds one copy.  A
    lane of length 0 reads nothing and returns zeros."""
    return _entry(float(softcap), bool(interpret))(
        q, new, pool, layer, block_tables, lengths, wb, wo)
