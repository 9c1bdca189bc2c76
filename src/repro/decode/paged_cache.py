"""Paged KV-cache management: refcounted block allocator + prefix index.

The dense per-batch ``cache_len`` buffers of the legacy serving path become a
pool of ``num_blocks`` fixed-size physical blocks per attention layer.  A
sequence owns a *block table* — logical block j of the sequence maps to
physical block ``table[j]`` — so sequences of different lengths share one
pool with no per-batch reallocation, and a finished sequence's blocks return
to the free list immediately (the capacity lever behind in-flight joins).

PR 4 makes the pool a *shared* cache:

  * physical blocks are **refcounted** — ``share`` lets a new sequence map
    the cached head of its prompt onto blocks another sequence (live or
    retired) already filled, and ``free`` only recycles a block when its last
    reference drops;
  * a retired block whose token content is registered in the
    :class:`PrefixIndex` is not returned to the free list — it parks on an
    LRU *evictable* list, still matchable, and is reclaimed lazily when
    ``alloc`` runs out of never-used blocks (pressure evicts cold prefixes
    first);
  * the :class:`PrefixIndex` hashes token-id chunks at block granularity
    into parent-chained keys, so ``match`` finds the longest cached chain of
    full blocks — plus an optional *partial* match of the first divergent
    block, which the scheduler resolves with a copy-on-write block copy.

Physical block 0 is reserved as the *null block*: padded block-table entries
and the write slots of inactive batch lanes all point there.  Null-block
contents are garbage by design; attention masks them via per-sequence
lengths, so no separate validity plumbing is needed inside jitted code.

The pool itself reuses the model's dense cache factory:
``model.init_cache(num_blocks, block_size)`` yields the identical pytree
with leaves ``[..., P, bs, K, hd]`` — physical blocks where the dense layout
had (batch, position) — so sharding specs and the superblock scan structure
carry over unchanged.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

#: reserved physical block id — scratch target for padded/inactive writes
NULL_BLOCK = 0

#: chain-hash of the empty prefix (the root parent of every chain)
ROOT_HASH = 0


def chain_hashes(tokens, block_size: int) -> List[int]:
    """Block-hash chain of a token sequence — the cache-status sync wire
    format.  ``h_j = hash((h_{j-1},) + chunk_j)`` over complete
    ``block_size`` chunks, rooted at :data:`ROOT_HASH`.  Integer-tuple
    hashing is PYTHONHASHSEED-independent, so producer (PrefixIndex delta
    stream) and consumer (the placement layer's replica index) agree without
    shipping raw tokens."""
    toks = [int(t) for t in tokens]
    out: List[int] = []
    h = ROOT_HASH
    for j in range(len(toks) // block_size):
        h = hash((h,) + tuple(toks[j * block_size:(j + 1) * block_size]))
        out.append(h)
    return out


class BlockAllocator:
    """Refcounted free-list allocator over the physical block pool of one arm.

    Pure host-side bookkeeping (device arrays never see the free list).
    Invariants, property-tested in tests/test_decode.py: a block is never
    handed out twice while live, every fully-dereferenced block becomes
    allocatable again, ``NULL_BLOCK`` is never handed out (nor freeable), and
    ``free + evictable + live == num_blocks - 1`` at every step.

    ``on_evict(block, key)`` fires when ``alloc`` reclaims an evictable
    block, so the prefix index can drop the stale mapping before the block's
    contents are overwritten.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 on_evict: Optional[Callable[[int, object], None]] = None):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the null block)")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.on_evict = on_evict
        self._free: List[int] = list(range(num_blocks - 1, NULL_BLOCK, -1))
        self._ref: Dict[int, int] = {}            # live block -> refcount
        self._key: Dict[int, object] = {}         # block -> prefix-index key
        self._evictable: "OrderedDict[int, object]" = OrderedDict()

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def evictable_blocks(self) -> int:
        return len(self._evictable)

    @property
    def available_blocks(self) -> int:
        """Blocks an all-or-nothing ``alloc`` could hand out right now."""
        return len(self._free) + len(self._evictable)

    @property
    def used_blocks(self) -> int:
        return len(self._ref)

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= self.available_blocks

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop n fresh blocks (refcount 1 each), or None with NO side effect
        (no partial pops, no evictions) if the pool cannot cover all n.
        Never-used blocks go first; under shortage the least-recently-parked
        evictable blocks are reclaimed, dropping their prefix-index entries
        via ``on_evict``."""
        if n > self.available_blocks:
            return None
        ids: List[int] = []
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                b, key = self._evictable.popitem(last=False)   # LRU first
                del self._key[b]
                if self.on_evict is not None:
                    self.on_evict(b, key)
            self._ref[b] = 1
            ids.append(b)
        return ids

    def share(self, ids: Sequence[int]) -> None:
        """Take a reference on cached blocks (a prefix hit).  Live blocks
        gain a reference; evictable blocks resurrect (keeping their index
        key).  Sharing a free/unknown block is an error — its contents are
        not a cached prefix."""
        for b in ids:
            if b in self._ref:
                self._ref[b] += 1
            elif b in self._evictable:
                del self._evictable[b]
                self._ref[b] = 1
            else:
                raise ValueError(f"share of non-cached block {b}")

    def free(self, ids: Sequence[int]) -> None:
        """Drop one reference per id.  A block whose last reference drops
        parks on the evictable LRU if its content is registered in the
        prefix index, else returns to the free list.  Freeing the null
        block, a free block, or more references than were taken raises."""
        for b in ids:
            if b == NULL_BLOCK:
                raise ValueError("free of the reserved null block")
            if b not in self._ref:
                raise ValueError(f"double free / foreign block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                if b in self._key:
                    self._evictable[b] = self._key[b]      # parked as MRU
                else:
                    self._free.append(b)

    def register(self, block: int, key: object) -> None:
        """Attach a prefix-index key to a LIVE block: when its last
        reference drops it becomes evictable cache instead of free."""
        if block not in self._ref:
            raise ValueError(f"register of non-live block {block}")
        self._key[block] = key

    def blocks_for(self, n_tokens: int) -> int:
        """Physical blocks needed to hold n_tokens cache slots."""
        return -(-n_tokens // self.block_size)


class PrefixIndex:
    """Block-granularity prefix cache over token-id chunks.

    A cached sequence is a chain of keys ``key_j = (key_{j-1}, chunk_j)``
    where ``chunk_j`` is the tuple of ``block_size`` token ids filling
    logical block j (root parent is ``None``).  ``match`` walks the chain
    greedily; ``insert`` registers a retired/preempted lane's full blocks.

    The exact nested-tuple keys double as hashes (no collision handling
    needed at this scale) and the child map per parent is what enables the
    *partial* tail match: a cached block whose first R < block_size tokens
    equal the prompt's remaining tail can be copy-on-write-mapped, saving R
    prefill tokens at the cost of one block copy.
    """

    def __init__(self, block_size: int):
        self.block_size = block_size
        # parent key -> {chunk tuple -> physical block}
        self._children: Dict[object, Dict[Tuple[int, ...], int]] = {}
        # exact key -> chain hash, mirrored for the cache-status delta
        # stream: ``on_delta("add"|"drop", chain_hash)`` fires on every
        # registration / reclaim so the placement layer can keep a global
        # block-hash -> replica index without ever snapshotting the index.
        self._hashes: Dict[object, int] = {}
        self.on_delta = None  # type: Optional[callable]

    def _chain_hash(self, key: object) -> int:
        """Chain hash of a nested-tuple key — a pure function of the key
        (``chain_hashes`` on the flattened tokens gives the same value), so
        it can be recomputed even after a parent entry was dropped."""
        if key is None:
            return ROOT_HASH
        h = self._hashes.get(key)
        if h is None:
            parent, chunk = key
            h = hash((self._chain_hash(parent),) + chunk)
            self._hashes[key] = h
        return h

    def __len__(self) -> int:
        return sum(len(c) for c in self._children.values())

    def match_full(self, tokens) -> List[int]:
        """Longest cached full-block chain covering a *committed* history.

        Unlike :meth:`match` this may cover **every** complete block — there
        is no leave-one-token rule, because the caller (the cache-store ship
        path) already holds the first generated token and needs no tail
        prefill.  A trailing partial block (``len(tokens) % block_size``
        tokens) is never matchable and stays the caller's to ship; when the
        history is an exact block multiple, the receiver's next write lands
        in a *fresh* block, so covering the whole history is write-safe.
        """
        bs = self.block_size
        toks = [int(t) for t in tokens]
        full: List[int] = []
        parent = None
        pos = 0
        while pos + bs <= len(toks):
            chunk = tuple(toks[pos:pos + bs])
            child = self._children.get(parent, {}).get(chunk)
            if child is None:
                break
            full.append(child)
            parent = (parent, chunk)
            pos += bs
        return full

    def match(self, tokens) -> Tuple[List[int], Optional[Tuple[int, int]]]:
        """Longest cached head of ``tokens``.

        Returns ``(full_blocks, tail)``: ``full_blocks`` are chain blocks
        whose whole content is a prompt prefix (share these); ``tail`` is
        ``(block, R)`` when a child block's first ``R`` tokens extend the
        match partially (copy-on-write this one), else None.  At least one
        token is always left uncovered so the tail prefill produces the
        last-position logits that seed decoding.
        """
        bs = self.block_size
        toks = [int(t) for t in tokens]
        full: List[int] = []
        parent = None
        pos = 0
        # full blocks: stop before covering the whole prompt (leave >= 1)
        while pos + bs < len(toks):
            chunk = tuple(toks[pos:pos + bs])
            child = self._children.get(parent, {}).get(chunk)
            if child is None:
                break
            key = (parent, chunk)
            full.append(child)
            parent = key
            pos += bs
        # partial tail: best common-prefix child of the last matched key
        rem = toks[pos:]
        cap = len(rem) - 1                       # leave >= 1 token uncovered
        best_r, best_b = 0, None
        for chunk, block in self._children.get(parent, {}).items():
            r = 0
            for a, b in zip(chunk, rem[:cap]):
                if a != b:
                    break
                r += 1
            if r > best_r:
                best_r, best_b = r, block
        # best_r < bs always: a child matching a full bs tokens of rem would
        # have been taken by the full-block loop above (same children dict)
        if best_r > 0:
            return full, (best_b, best_r)
        return full, None

    def insert(self, tokens, block_ids: Sequence[int],
               alloc: BlockAllocator) -> int:
        """Register the full blocks of a committed token history.  Chunks
        already present keep their existing block (the newcomer's duplicate
        frees normally — no key, so it returns to the free list).  Returns
        the number of newly registered blocks."""
        bs = self.block_size
        toks = [int(t) for t in tokens]
        parent = None
        added = 0
        for j in range(len(toks) // bs):
            chunk = tuple(toks[j * bs:(j + 1) * bs])
            key = (parent, chunk)
            kids = self._children.setdefault(parent, {})
            if chunk not in kids:
                kids[chunk] = block_ids[j]
                alloc.register(block_ids[j], key)
                added += 1
                if self.on_delta is not None:
                    self.on_delta("add", self._chain_hash(key))
            parent = key
        return added

    def drop(self, key: object) -> None:
        """Forget one mapping (its block is being reclaimed)."""
        parent, chunk = key
        kids = self._children.get(parent)
        if kids is not None and chunk in kids:
            del kids[chunk]
            if not kids:
                del self._children[parent]
            if self.on_delta is not None:
                self.on_delta("drop", self._chain_hash(key))
        self._hashes.pop(key, None)


def quantize_kv(x):
    """Symmetric per-token int8 quantization of K/V vectors [..., hd]:
    one f32 scale per (token, kv head) — the amax over the head dim — so a
    single-token decode write never rescales neighbouring slots.  Returns
    ``(codes int8 [..., hd], scales f32 [...])``; dequant is
    ``codes * scales[..., None]``."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = amax / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xf / safe[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def quantize_pool(pool):
    """Convert a freshly initialized f32 paged pool to the int8 layout.

    Every attention pool entry ``{"k", "v"}`` (leaves [..., P, bs, K, hd])
    becomes ``{"k" int8, "k_scale" f32 [..., P, bs, K], "v", "v_scale"}`` —
    int8 codes plus one symmetric scale per token slot per kv head.  The
    allocator / prefix index / block tables never look inside blocks, so
    they are untouched; ``copy_blocks`` and the write paths key off the
    ``_scale`` leaves.  Capacity math: a token slot shrinks from ``4*hd``
    to ``hd + 4`` bytes per kv head (:func:`int8_kv_capacity_ratio`).
    """
    def conv(node):
        if isinstance(node, dict):
            if set(node) == {"k", "v"}:
                return {
                    "k": jnp.zeros(node["k"].shape, jnp.int8),
                    "k_scale": jnp.zeros(node["k"].shape[:-1], jnp.float32),
                    "v": jnp.zeros(node["v"].shape, jnp.int8),
                    "v_scale": jnp.zeros(node["v"].shape[:-1], jnp.float32),
                }
            return {k: conv(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(conv(v) for v in node)
        return node

    return conv(pool)


def int8_kv_capacity_ratio(head_dim: int, scale_bytes: int = 4) -> float:
    """Effective-capacity multiplier of the int8 KV layout over f32: an f32
    token slot is ``4*hd`` bytes per kv head, an int8 slot ``hd`` code bytes
    plus one f32 scale — ``4*hd / (hd + 4)`` (3.56x at hd=32, ->4x as hd
    grows; >= 1.9x for every hd >= 4)."""
    return (4.0 * head_dim) / (head_dim + scale_bytes)


def is_scale_path(path) -> bool:
    """Whether a pool leaf's tree path names an int8 scale leaf
    (``"k_scale"``/``"v_scale"``), as opposed to a K/V leaf."""
    last = path[-1]
    name = getattr(last, "key", None)
    return isinstance(name, str) and name.endswith("_scale")


def pool_block_bytes(pool) -> int:
    """Pool bytes per physical block, summed over every layer/leaf — the
    denominator of the effective-capacity telemetry.  Scale leaves
    ([..., P, bs, K]) have their physical axis at -3, KV leaves at -4."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(pool)[0]:
        p = leaf.shape[-3] if is_scale_path(path) else leaf.shape[-4]
        total += (leaf.size // p) * leaf.dtype.itemsize
    return total


def copy_blocks(pool, src: jax.Array, dst: jax.Array):
    """Copy physical blocks ``dst[i] := src[i]`` in every pool leaf — the
    copy-on-write resolve for a partially matched block.  ``src``/``dst``:
    [n] int32; padded pairs point both ids at the null scratch block.
    Layout-agnostic: int8 code leaves copy bit-exactly and their per-slot
    scale leaves ([..., P, bs, K], physical axis -3) ride along, so a COW'd
    quantized block never requantizes."""
    def leaf(path, x):
        if is_scale_path(path):
            return x.at[..., dst, :, :].set(x[..., src, :, :])
        return x.at[..., dst, :, :, :].set(x[..., src, :, :, :])

    return jax.tree_util.tree_map_with_path(leaf, pool)


def gather_blocks(pool, ids: jax.Array):
    """Extract the payload of physical blocks ``ids`` ([n] int32) from every
    pool leaf — the wire format of a cache-store shipment.  Returns a pytree
    with the pool's structure whose leaves have the physical axis replaced
    by ``n``.  Layout-agnostic like :func:`copy_blocks`: int8 code leaves
    and their per-token-slot ``_scale`` leaves are extracted verbatim, so a
    shipped quantized block is never requantized in flight."""
    def leaf(path, x):
        if is_scale_path(path):
            return x[..., ids, :, :]
        return x[..., ids, :, :, :]

    return jax.tree_util.tree_map_with_path(leaf, pool)


def scatter_blocks(pool, payload, ids: jax.Array):
    """Write a :func:`gather_blocks` payload into physical blocks ``ids`` of
    ``pool`` — the receiver half of a block shipment.  Padded entries point
    at the null scratch block (whose contents are garbage by design), so one
    unconditional scatter serves any pow2-bucketed wave width."""
    def leaf(path, x, p):
        if is_scale_path(path):
            return x.at[..., ids, :, :].set(p)
        return x.at[..., ids, :, :, :].set(p)

    return jax.tree_util.tree_map_with_path(leaf, pool, payload)


def write_slots(lengths: jax.Array, block_tables: jax.Array,
                active: jax.Array, block_size: int):
    """(physical block, in-block offset) for each lane's next token write.

    ``lengths``: [B] tokens already in cache (the write position);
    ``block_tables``: [B, NB]; ``active``: [B] bool.  Inactive lanes route to
    the null block so the jitted decode scan issues one unconditional
    scatter.  Distinct active lanes own distinct write blocks (shared prefix
    blocks are never write targets), so the scatter never collides except on
    the null scratch block.
    """
    b = lengths.shape[0]
    logical = lengths // block_size
    wb = block_tables[jnp.arange(b), jnp.clip(
        logical, 0, block_tables.shape[1] - 1)]
    wo = lengths % block_size
    wb = jnp.where(active, wb, NULL_BLOCK)
    wo = jnp.where(active, wo, 0)
    return wb.astype(jnp.int32), wo.astype(jnp.int32)


def chunk_write_slots(starts: jax.Array, n_tok: jax.Array,
                      block_tables: jax.Array, block_size: int, chunk: int):
    """Per-token write slots for one prefill chunk.

    ``starts``: [B] absolute position of each lane's first chunk token;
    ``n_tok``: [B] valid tokens this chunk (<= chunk); padded token slots
    and idle lanes route to the null block.  Returns (wb, wo): [B, chunk].
    """
    b = starts.shape[0]
    pos = starts[:, None] + jnp.arange(chunk)[None, :]        # [B, C]
    valid = jnp.arange(chunk)[None, :] < n_tok[:, None]
    logical = jnp.clip(pos // block_size, 0, block_tables.shape[1] - 1)
    wb = jnp.take_along_axis(block_tables, logical, axis=1)
    wb = jnp.where(valid, wb, NULL_BLOCK)
    wo = jnp.where(valid, pos % block_size, 0)
    return wb.astype(jnp.int32), wo.astype(jnp.int32)
