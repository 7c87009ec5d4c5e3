"""Paged/blocked KV-cache for autoregressive serving (docs/serving.md).

**Four kinds of per-layer cache** (:class:`CacheSpec`), one allocator
for all of them: ``paged_kv`` (softmax attention: K and V rows of
``kv_heads * head_dim`` lanes in two pools), ``paged_latent`` (latent
attention: ONE pool whose row is the compressed ``[c | k_r]`` all heads
share, under the same tables and the same ``write_*`` scatters),
``recurrent_state`` (one fixed-size float32 state a request, a slot and
no table) and ``paged_window`` (a sliding-window layer's K and V rows in
a RING of blocks a request, :class:`WindowAllocator`; a model may mix it
with ``paged_kv`` global layers, whose rows are in pools of their own
under a table that grows).  Any other mix is refused by name.

vLLM-style paging on top of the repo's blockwise-attention machinery:
key/value states live in **preallocated device pools** of fixed-size
blocks, and each in-flight request owns a host-side **block table** —
logical block ``j`` of the request maps to physical pool slot
``table[j]``.  Slots are recycled the moment a request finishes, so HBM
for the cache is bounded by the pool, not by max-batch × max-seq-len.

**The stored form** (:func:`make_pools`, the one place that decides it):
``[num_layers, num_blocks, block_size, heads * head_dim]`` — a position's
heads side by side on the minor dimension.  That dimension is what a TPU
lays along its 128 lanes: at ``heads * head_dim`` a multiple of 128 and a
block one sublane tile deep, XLA's default layout of the buffer and the
layout the Pallas kernel's block pipeline wants are the same bytes, so a
donated pool crosses every program's and every kernel's boundary
untouched (a ``(..., heads, head_dim)`` minor pair with ``head_dim`` 64
fills half of every lane row, and was re-laid-out whole on entry to and
exit from each program).  No helper here ever takes one layer's slice of
a pool: readers get the whole pool and the layer, and put the layer among
the indices of their gather (or of the kernel's block map); writers
scatter at ``[layer, slot, offset]``.  The head geometry is never read
off a pool: it comes with the query states (``[..., H, hd]``).  A latent
pool is ``[num_layers, num_blocks, block_size, lanes]`` with ``lanes``
the row's ``kv_lora_rank + rope`` values rounded up to whole 128-lane
rows (Mosaic copies whole rows out of HBM): 576 values on 640 lanes.

The device side is three pure functions, all shape-static so the serve
engine's decode program never retraces:

* :func:`paged_attention` — one query token per request attends over its
  table-addressed blocks.  The reference ``impl="scan"`` runs the same
  online-softmax block scan as
  ``parallel/ring_attention.blockwise_attention`` / the flash kernels
  (running max / sum / accumulator in f32, ``NEG_INF`` masking);
  ``impl="dense"`` gathers all blocks at once for thunk-bound backends,
  and ``impl="flash"`` dispatches the Pallas flash-decode kernel
  (``serve/flash_decode.py``).
* :func:`paged_prefill_attention` — causal attention for one **prefill
  chunk** (round-12 chunked prefill): C query positions against the
  request's whole cached prefix.
* :func:`write_prefill` / :func:`write_decode` — functional scatters of
  freshly-computed K/V states into table-addressed slots.  Padded or
  inactive rows are redirected to the reserved **trash block 0** so the
  scatter itself stays branch-free.

Round-12 adds **fp8-e4m3 quantized pools** (:class:`QuantPool`): the
payload stores 1 byte/element plus one f32 scale per cached position
(``quant.rowwise_quantize`` — the KV variant of the r9 block-scale
machinery), halving cache bytes per token; every read path dequantizes
to f32 at the gather.

The host side is :class:`BlockAllocator`: a free-list allocator with
alloc/free/defrag and per-request ownership tracking (table integrity is
checkable at any time via :meth:`BlockAllocator.check`).

Bitwise note (docs/perf.md r7 applies): :func:`dense_attention` runs the
*same* block scan over a contiguous cache, so paged-vs-dense parity is
exact — the paging indirection is a pure gather of identical values at
identical shapes.
"""
from __future__ import annotations

import functools
import hashlib
from collections import OrderedDict
from typing import (Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from ..parallel.flash_attention import NEG_INF
from .. import quant as quantmod

__all__ = ["TRASH_BLOCK", "KV_QUANT_FORMATS", "QuantPool", "BlockAllocator",
           "PAGED_KV", "RECURRENT_STATE", "PAGED_LATENT", "PAGED_WINDOW",
           "CacheSpec", "WindowAllocator", "ring_width",
           "gqa_prefill_attention", "gqa_decode_attention",
           "make_state_pool", "latent_lanes", "latent_decode_attention",
           "latent_prefill_attention",
           "PrefixIndex", "make_pools", "is_quantized",
           "pool_nbytes", "kv_bytes_per_token", "softmax_scale",
           "paged_attention",
           "paged_prefill_attention", "paged_verify_attention",
           "dense_attention", "write_prefill", "write_decode", "write_spec",
           "scrub_positions", "compact_pool"]

#: physical slot 0 is never handed out: padded prefill positions and
#: inactive decode rows scatter their garbage there, keeping every
#: device-side write unconditional (no retrace-prone masking branches).
TRASH_BLOCK = 0

#: the four kinds of per-layer cache (:class:`CacheSpec`)
PAGED_KV = "paged_kv"                 # K and V rows that grow with the sequence
RECURRENT_STATE = "recurrent_state"   # one fixed-size state a request
PAGED_LATENT = "paged_latent"         # one compressed row a position, paged
PAGED_WINDOW = "paged_window"         # K and V rows of a window, in a ring

#: supported quantized-pool storage formats ("fp8" = e4m3 payload + one
#: f32 scale per cached position; see :class:`QuantPool`).
KV_QUANT_FORMATS = ("fp8",)

#: fp8 wire format used for quantized pools — e4m3 (the activation
#: format of the r9 compute policy): KV states are forward-path values,
#: so mantissa beats the e5m2 dynamic range.
KV_FP8_FORMAT = "e4m3"


class QuantPool(NamedTuple):
    """A quantized KV pool: fp8-e4m3 payload plus per-position f32
    scales, quantized with :func:`mxnet_tpu.quant.rowwise_quantize` (one
    scale per cached token position per layer — the row absmax lands on
    the fp8 format max, so the cast never overflows).

    ``payload``: the stored form of :func:`make_pools` in fp8;
    ``scale``: ``[num_layers, num_blocks, block_size]`` f32.  A
    NamedTuple so the pair rides through jit/donation as one pytree —
    every pool-taking function here accepts either a plain array pool or
    a ``QuantPool`` and dispatches on the type.
    """
    payload: jax.Array
    scale: jax.Array


Pool = Union[jax.Array, QuantPool]


def _scoped(name: str):
    """Run the decorated helper under ``jax.named_scope(name)``, so the
    device trace's op metadata says which part of a serving program an
    instruction belongs to.  A fresh scope object per call: the one
    ``jax.named_scope`` returns keeps state and is not re-entrant."""
    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return deco


def is_quantized(pool) -> bool:
    return isinstance(pool, QuantPool)


def pool_nbytes(*pools: Pool) -> int:
    """Device bytes held by the given pools (payload + scales)."""
    total = 0
    for pool in pools:
        for leaf in jax.tree_util.tree_leaves(pool):
            total += int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
    return total


def latent_lanes(latent_width: int) -> int:
    """Lanes a latent row is stored on: whole 128-lane rows."""
    return -(-int(latent_width) // 128) * 128


def kv_bytes_per_token(num_layers: int, heads: int, head_dim: int,
                       quant: Optional[str] = None,
                       dtype=jnp.float32,
                       latent_width: Optional[int] = None) -> int:
    """HBM bytes one cached token position occupies across both pools
    (K and V, all layers) — the number the decode path streams per
    token per request.  fp8 pools pay 1 byte/element plus one f32 scale
    per (layer, position, pool).  ``latent_width`` (a latent model's
    ``kv_lora_rank + rope``): the ONE pool's row as stored, padding
    lanes included, since the kernel moves them."""
    if latent_width is not None:
        return (num_layers * latent_lanes(latent_width)
                * jnp.dtype(dtype).itemsize)
    per_pos = heads * head_dim
    if quant is None:
        return 2 * num_layers * per_pos * jnp.dtype(dtype).itemsize
    if quant not in KV_QUANT_FORMATS:
        raise MXNetError(f"unknown kv quant format {quant!r}, expected one "
                         f"of {KV_QUANT_FORMATS} or None")
    return 2 * num_layers * (per_pos * 1 + 4)


# ---------------------------------------------------------------------------
# What a model caches, layer by layer
# ---------------------------------------------------------------------------

class CacheSpec(NamedTuple):
    """The cache kind of every layer of a model.

    ``paged_kv`` (softmax attention): the layer's K and V rows live in
    the block pools of :func:`make_pools`; a request owns
    ``ceil(tokens / block_size)`` blocks and grows by one as it decodes.

    ``paged_latent`` (latent attention): the layer's compressed rows
    (``[c | k_r]``, shared by all heads) live in the ONE pool
    :func:`make_pools` makes for a ``latent_width``, under the same
    blocks, tables and growth as ``paged_kv``.

    ``recurrent_state`` (power retention): the layer keeps one
    fixed-size float32 state a request, in the pool of
    :func:`make_state_pool`; a request owns ONE state slot from
    admission to finish, cancel, failure or preemption, whatever its
    length.

    All are handed out by the one :class:`BlockAllocator`: a state
    slot is a physical slot that holds any number of tokens
    (``block_size`` = the engine's ``max_seq_len``), so ``num_used``,
    ``check`` and the engine's drain check see slots as they see
    blocks, and slot 0 is the trash slot of both.  A slot's contents
    are discarded before reuse: the first prefill chunk of a request
    (``start == 0``) reads zeros in place of whatever the slot held.

    ``paged_window`` (a sliding-window layer): the layer's K and V rows
    live in pools of their own, under a request's WINDOW table, a ring
    of at most :func:`ring_width` blocks (:class:`WindowAllocator`);
    the model's global (``paged_kv``) layers, if any, keep theirs in
    pools of their own under the request's growing table.
    """
    kinds: Tuple[str, ...]

    @classmethod
    def for_attention(cls, attention_kinds: Sequence[str]) -> "CacheSpec":
        from ..models.decoder import LATENT, POWER_RETENTION, SLIDING, SOFTMAX
        table = {SOFTMAX: PAGED_KV, SLIDING: PAGED_WINDOW,
                 POWER_RETENTION: RECURRENT_STATE, LATENT: PAGED_LATENT}
        return cls(tuple(table[k] for k in attention_kinds))

    @property
    def kind(self) -> str:
        """The kind of the model's cache: the one kind all layers are of,
        or ``paged_window`` for window layers beside global ones (two
        tables a request, one allocator).  Any other mix is refused:
        paged_kv with paged_latent needs two pools of different rows
        under one table, and either with recurrent_state needs blocks
        AND a slot a request; no model here asks for one yet."""
        kinds = set(self.kinds)
        if kinds == {PAGED_WINDOW, PAGED_KV}:
            return PAGED_WINDOW
        if len(kinds) != 1:
            raise MXNetError(
                f"a model mixing cache kinds {sorted(kinds)} is not served "
                "yet: every layer must be paged_kv, every layer "
                "paged_latent, every layer recurrent_state, or window "
                "layers (paged_window) beside paged_kv ones")
        return self.kinds[0]

    @property
    def recurrent(self) -> bool:
        """Every layer keeps a recurrent state (False: every layer is
        paged, K/V or latent; see :attr:`kind` for the mix)."""
        return self.kind == RECURRENT_STATE


def make_state_pool(num_layers: int, num_slots: int, kv_heads: int,
                    head_dim: int) -> jax.Array:
    """The recurrent-state pool, zeroed: ``[num_layers, num_slots,
    kv_heads, chunks, rows, head_dim]`` float32, where ``(chunks, rows,
    head_dim)`` is one head's ``S`` and ``z`` side by side in the layout
    of ``models/retention.py``.  Slot 0 is the trash slot."""
    from ..models.retention import state_shape
    return jnp.zeros((num_layers, num_slots, kv_heads)
                     + state_shape(head_dim), jnp.float32)


# ---------------------------------------------------------------------------
# Host side: block allocator
# ---------------------------------------------------------------------------

class BlockAllocator:
    """Free-list allocator over the physical slots of a KV pool, with
    reference counting and an LRU side-cache of refcount-0 blocks.

    Slot ``TRASH_BLOCK`` (0) is reserved.  ``alloc`` hands out the
    lowest free slots (deterministic — replays identically),
    ``release`` drops one owner's reference, ``defrag`` compacts live
    slots toward the low end of the pool and returns the relocation map
    the engine applies with :func:`compact_pool`.

    A physical slot is in exactly one of three states:

    * **free** — on the free list, contents garbage.
    * **referenced** — held by one or more owners (``addref`` lets a
      second request map a slot another request already filled — the
      prefix cache's copy-on-write sharing; writes only ever target
      refcount-1 private blocks, so "copy" is structural: a diverging
      request allocates fresh blocks past the shared prefix).
    * **cached** — refcount dropped to zero but ``cache_filter`` kept
      the slot resident (its KV contents are indexed by content hash).
      Cached slots are *extra capacity, never pressure*: ``alloc``
      evicts the coldest cached slots (LRU) before failing, and
      ``num_available``/``can_alloc`` count them as allocatable, so
      caching never causes an admission reject or preemption that
      would not have happened anyway.

    ``cache_filter(block) -> bool`` and ``on_evict(block)`` are
    settable attributes (not ctor args) so the engine can wire the
    allocator and :class:`PrefixIndex` together after both exist.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 cache_cap: Optional[int] = None):
        if num_blocks < 2:
            raise MXNetError("BlockAllocator needs >= 2 blocks "
                             "(slot 0 is the reserved trash block)")
        if block_size < 1:
            raise MXNetError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._free: List[int] = list(range(1, num_blocks))
        self._refs: Dict[int, set] = {}        # phys slot -> owner set
        self._cached: "OrderedDict[int, None]" = OrderedDict()  # LRU order
        self.cache_cap = cache_cap             # max cached slots (None = all)
        self.cache_filter: Optional[Callable[[int], bool]] = None
        self.on_evict: Optional[Callable[[int], None]] = None

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return len(self._refs)

    @property
    def num_cached(self) -> int:
        return len(self._cached)

    @property
    def num_available(self) -> int:
        """Slots allocatable right now: free plus evictable cached."""
        return len(self._free) + len(self._cached)

    def blocks_for_tokens(self, ntokens: int) -> int:
        """Blocks needed to hold ``ntokens`` cache entries."""
        return max(1, -(-int(ntokens) // self.block_size))

    def can_alloc(self, nblocks: int) -> bool:
        return nblocks <= self.num_available

    def _evict_one(self) -> None:
        block, _ = self._cached.popitem(last=False)   # coldest first
        if self.on_evict is not None:
            self.on_evict(block)
        self._free.append(block)

    def alloc(self, nblocks: int, owner) -> List[int]:
        if nblocks > self.num_available:
            raise MXNetError(
                f"kv pool exhausted: want {nblocks} blocks, "
                f"{len(self._free)} free + {len(self._cached)} cached "
                f"of {self.num_blocks - 1}")
        while nblocks > len(self._free):
            self._evict_one()
        self._free.sort()
        got, self._free = self._free[:nblocks], self._free[nblocks:]
        for b in got:
            self._refs[b] = {owner}
        return got

    def addref(self, block: int, owner) -> None:
        """Map an already-resident slot into another owner's table —
        promotes a cached slot back to referenced, or adds an owner to
        a shared referenced slot.  Free slots cannot be addref'd."""
        if block in self._cached:
            del self._cached[block]
            self._refs[block] = {owner}
            return
        refs = self._refs.get(block)
        if refs is None:
            raise MXNetError(f"addref of free kv block {block}")
        if owner in refs:
            raise MXNetError(f"owner {owner!r} already references "
                             f"kv block {block}")
        refs.add(owner)

    def refcount(self, block: int) -> int:
        return len(self._refs.get(block, ()))

    def release(self, blocks: Sequence[int], owner) -> None:
        """Drop ``owner``'s reference on each slot.  A slot whose last
        reference drops either parks in the LRU cache (``cache_filter``
        says its contents are worth keeping) or returns to the free
        list."""
        for b in blocks:
            refs = self._refs.get(b)
            if refs is None or owner not in refs:
                raise MXNetError(
                    f"release of kv block {b} not held by {owner!r}")
            refs.discard(owner)
            if refs:
                continue
            del self._refs[b]
            if self.cache_filter is not None and self.cache_filter(b):
                self._cached[b] = None          # MRU end
                if self.cache_cap is not None:
                    while len(self._cached) > self.cache_cap:
                        self._evict_one()
            else:
                self._free.append(b)

    def uncache(self, blocks: Sequence[int]) -> None:
        """Return cached slots straight to the free list *without* the
        ``on_evict`` callback — the invalidation path, where the index
        has already dropped them.  Unknown slots are ignored."""
        for b in blocks:
            if b in self._cached:
                del self._cached[b]
                self._free.append(b)

    def free(self, blocks: Sequence[int]) -> None:
        """Force-drop slots back to the free list regardless of
        refcount (legacy single-owner path; callers must not share).
        Cached slots are evicted through ``on_evict`` first."""
        for b in blocks:
            if b in self._refs:
                del self._refs[b]
                self._free.append(b)
            elif b in self._cached:
                del self._cached[b]
                if self.on_evict is not None:
                    self.on_evict(b)
                self._free.append(b)
            else:
                raise MXNetError(f"double free of kv block {b}")

    def owned_by(self, owner) -> List[int]:
        return sorted(b for b, refs in self._refs.items() if owner in refs)

    def check(self, tables: Dict[object, Sequence[int]]) -> None:
        """Table-integrity audit: every table entry is a referenced
        slot held by that mapper, a slot in several tables is legal iff
        *each* mapper holds a reference (prefix sharing), cached and
        free slots appear in no table, and every (slot, owner)
        reference appears in that owner's table."""
        seen: Dict[int, List[object]] = {}
        free = set(self._free)
        for owner, table in tables.items():
            for b in table:
                if b == TRASH_BLOCK:
                    raise MXNetError(f"{owner!r}: table points at the "
                                     "trash block")
                if b in free:
                    raise MXNetError(f"block {b} both free and mapped")
                if b in self._cached:
                    raise MXNetError(f"block {b} both cached (ref-0) "
                                     f"and mapped by {owner!r}")
                refs = self._refs.get(b, ())
                if owner not in refs:
                    raise MXNetError(f"{owner!r}: block {b} not owned "
                                     f"(holders={sorted(map(repr, refs))})")
                seen.setdefault(b, []).append(owner)
        leaked = sorted(
            (b, o) for b, refs in self._refs.items() for o in refs
            if o not in seen.get(b, ()))
        if leaked:
            raise MXNetError(f"leaked blocks (owned, not in any table): "
                             f"{leaked}")

    def defrag(self) -> Dict[int, int]:
        """Compact live slots (referenced *and* cached — cached blocks
        hold reusable KV) to the lowest physical indices.  Returns
        ``{old_slot: new_slot}`` for every *moved* slot; the caller must
        rewrite its tables, remap the prefix index, and apply
        :func:`compact_pool` with the same map before the next device
        step.  LRU order of cached slots is preserved."""
        live = sorted(set(self._refs) | set(self._cached))
        mapping: Dict[int, int] = {}
        target = 1
        for b in live:
            if b != target:
                mapping[b] = target
            target += 1
        if mapping:
            self._refs = {mapping.get(b, b): o
                          for b, o in self._refs.items()}
            self._cached = OrderedDict(
                (mapping.get(b, b), None) for b in self._cached)
            self._free = list(range(1 + len(live), self.num_blocks))
        return mapping


def ring_width(window: int, chunk: int, block_size: int) -> int:
    """Blocks a request's window table holds: every position a chunk's
    queries may see, from ``window - 1`` before its first query to its
    last, on whatever block boundaries they fall: ``ceil((window +
    chunk) / block_size) + 1``.  Position ``p`` lives in column ``(p //
    block_size) % ring``, so the block a write reuses last held
    positions that no query of the chunk (or of a decode step, ``chunk``
    1) can see any more."""
    return -(-(int(window) + int(chunk)) // int(block_size)) + 1


class WindowAllocator(BlockAllocator):
    """The one allocator of a model with window layers (kind
    ``paged_window``): itself the allocator of the GLOBAL layers' pools,
    whose table a request grows by a block every ``block_size``
    positions, with ``window``, the allocator of the WINDOW layers'
    pools, beside it.  A request's window table is a ring of
    :func:`ring_width` blocks at most: once it holds ``ring`` of them it
    takes no more, and the block of the position ``ring`` blocks back is
    overwritten in place.  ``num_used`` and :meth:`check` count both
    kinds, so admission, preemption and the engine's drain check see
    both.  No prefix sharing: a window block holds different positions
    over a request's life."""

    def __init__(self, num_blocks: int, window_blocks: int, block_size: int,
                 ring: int):
        super().__init__(num_blocks, block_size)
        self.window = BlockAllocator(window_blocks, block_size)
        self.ring = int(ring)

    @property
    def global_used(self) -> int:
        return len(self._refs)

    @property
    def num_used(self) -> int:
        return self.global_used + self.window.num_used

    def ring_blocks(self, ntokens: int) -> int:
        """Window blocks a request of ``ntokens`` positions holds."""
        return min(self.ring, self.blocks_for_tokens(ntokens))

    def check(self, tables: Dict[object, Sequence[int]],
              rings: Optional[Dict[object, Sequence[int]]] = None) -> None:
        """Both audits of :meth:`BlockAllocator.check`, and no ring wider
        than ``ring``."""
        super().check(tables)
        rings = rings or {}
        for owner, ring in rings.items():
            if len(ring) > self.ring:
                raise MXNetError(f"{owner!r}: a window table of {len(ring)} "
                                 f"blocks, the ring is {self.ring}")
        self.window.check(rings)


# ---------------------------------------------------------------------------
# Host side: content-hashed prefix index
# ---------------------------------------------------------------------------

class PrefixIndex:
    """Content hash -> physical slot map for cross-request KV reuse
    (docs/serving.md §Prefix cache).

    Each *full* block of a token sequence gets a rolling chain hash:
    ``h_j = blake2b(h_{j-1} | weights_version | tokens_of_block_j)``.
    Chaining makes the hash position- and prefix-dependent, so equal
    token windows at different depths never collide, and folding the
    weights version in means a weight swap invalidates every entry at
    once (``invalidate`` bumps the version — stale hashes become
    unreachable even before the map is cleared).

    The index stores only the hash->slot map; residency/refcounts live
    in :class:`BlockAllocator` (``cache_filter=index.contains_block``
    keeps indexed blocks resident at refcount 0, ``on_evict=
    index.drop_block`` unpublishes them when LRU pressure reclaims the
    slot).  Partial (tail) blocks are never published: only full,
    prefill-written blocks are content-addressable, which is what makes
    sharing copy-on-write-safe — every later write lands strictly past
    the last full prefix block.
    """

    def __init__(self, block_size: int):
        if block_size < 1:
            raise MXNetError(f"block_size must be >= 1, got {block_size}")
        self.block_size = int(block_size)
        self.version = 0
        self._entries: Dict[bytes, int] = {}      # chain hash -> phys slot
        self._block_hash: Dict[int, bytes] = {}   # phys slot -> chain hash

    def __len__(self) -> int:
        return len(self._entries)

    def chain_hashes(self, tokens: Sequence[int]) -> List[bytes]:
        """Rolling chain hash of every *full* block of ``tokens``
        (``len(tokens) // block_size`` digests; the partial tail is
        never hashed)."""
        bs = self.block_size
        ver = self.version.to_bytes(8, "little")
        out: List[bytes] = []
        prev = b"\x00" * 16
        for j in range(len(tokens) // bs):
            blk = np.asarray(tokens[j * bs:(j + 1) * bs], np.int64).tobytes()
            prev = hashlib.blake2b(prev + ver + blk,
                                   digest_size=16).digest()
            out.append(prev)
        return out

    def match(self, tokens: Sequence[int]) -> List[int]:
        """Longest indexed prefix: physical slots for the leading run
        of full blocks whose chain hashes are all present (stops at the
        first miss — the chain guarantees no gaps)."""
        blocks: List[int] = []
        for h in self.chain_hashes(tokens):
            b = self._entries.get(h)
            if b is None:
                break
            blocks.append(b)
        return blocks

    def publish(self, h: bytes, block: int) -> bool:
        """Register ``block`` as the canonical holder of chain hash
        ``h``.  First publisher wins: a duplicate hash (another request
        prefilled the same prefix in the same step) leaves the existing
        entry — the late block simply stays private and unshared.
        Returns whether the entry was inserted."""
        if h in self._entries or block in self._block_hash:
            return False
        self._entries[h] = block
        self._block_hash[block] = h
        return True

    def contains_block(self, block: int) -> bool:
        return block in self._block_hash

    def drop_block(self, block: int) -> None:
        """Unpublish one slot (LRU eviction / force-free).  Safe no-op
        for unindexed slots."""
        h = self._block_hash.pop(block, None)
        if h is not None:
            self._entries.pop(h, None)

    def invalidate(self) -> List[int]:
        """Drop every entry and bump the weights version (weight swap:
        resident KV no longer matches the model).  Returns the slots
        that were indexed so the caller can ``uncache`` them."""
        dropped = sorted(self._block_hash)
        self.version += 1
        self._entries.clear()
        self._block_hash.clear()
        return dropped

    def remap(self, mapping: Dict[int, int]) -> None:
        """Apply a :meth:`BlockAllocator.defrag` relocation map."""
        if not mapping:
            return
        self._entries = {h: mapping.get(b, b)
                         for h, b in self._entries.items()}
        self._block_hash = {mapping.get(b, b): h
                            for b, h in self._block_hash.items()}


# ---------------------------------------------------------------------------
# Device side: pools + paged reads/writes
# ---------------------------------------------------------------------------

def make_pools(num_layers: int, num_blocks: int, block_size: int,
               heads: int, head_dim: int, dtype=jnp.float32,
               quant: Optional[str] = None,
               latent_width: Optional[int] = None) -> Tuple[Pool, ...]:
    """Preallocate the K and V pools in the stored form:
    ``[num_layers, num_blocks, block_size, heads * head_dim]`` (the
    module docstring says why the heads are flattened onto the minor
    dimension: lanes).

    ``latent_width`` (a latent model's ``kv_lora_rank + rope``): ONE
    pool, ``[num_layers, num_blocks, block_size,
    latent_lanes(latent_width)]``: a row all heads share, its first
    ``kv_lora_rank`` lanes the values too, padded with zero lanes to
    whole 128-lane rows.  Returned as a 1-tuple; never quantized.

    ``quant="fp8"`` returns :class:`QuantPool` pairs instead — e4m3
    payload plus per-position f32 scales — halving cache bytes per token
    (4B -> 1B payload + amortized scale).  Each pool gets its own fresh
    buffers: the engine donates both, and aliased donations are illegal.
    """
    if latent_width is not None:
        if quant is not None:
            raise MXNetError("a latent pool is not quantized (kv_quant)")
        return (jnp.zeros((num_layers, num_blocks, block_size,
                           latent_lanes(latent_width)), dtype),)
    shape = (num_layers, num_blocks, block_size, heads * head_dim)
    if quant is None:
        return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
    if quant not in KV_QUANT_FORMATS:
        raise MXNetError(f"unknown kv quant format {quant!r}, expected one "
                         f"of {KV_QUANT_FORMATS} or None")
    fp8 = quantmod._FP8_DTYPES[KV_FP8_FORMAT]
    def one():
        return QuantPool(jnp.zeros(shape, fp8),
                         jnp.zeros(shape[:3], jnp.float32))
    return one(), one()


def softmax_scale(head_dim: int, scale: Optional[float] = None) -> np.float32:
    """The scores' scale (default ``1/sqrt(head_dim)``) as a **float32**
    scalar.  The package runs with ``jax_enable_x64`` on, and an
    ``np.float64`` scalar is not weakly typed: ``1.0 / np.sqrt(d)`` met
    the f32 scores and promoted the whole masked softmax and both
    contractions to float64, which a TPU emulates in ``while`` loops
    (ISSUE 25: 448 of the prefill chunk's 500 ms).  Every attention of
    the serving tier takes its scale from here;
    tests/test_no_float64.py walks the programs."""
    return np.float32(1.0 / np.sqrt(head_dim) if scale is None else scale)


def _block_size_of(pool: Pool) -> int:
    return (pool.payload if is_quantized(pool) else pool).shape[2]


@_scoped("pool_read")
def _gather_blocks(pool: Pool, layer: int, idx, shape):
    """Gather physical blocks of one layer by slot index, dequantizing
    fp8 payloads to f32 against their per-position scales, as ``shape``
    (``[..., positions, H, hd]``: what the per-head contractions want).
    ``idx`` may be any int shape.

    The layer rides among the gather's indices (``pool[layer, idx]`` is
    ONE gather out of the whole pool), so no ``[num_blocks, BS, ...]``
    slice of a layer is ever materialised.  Un-flattening the gathered
    rows' ``H * hd`` lanes into heads re-lays-out the gathered rows (not
    the pool), which is this scope's larger half on a TPU."""
    if is_quantized(pool):
        rows = (pool.payload[layer, idx].astype(jnp.float32)
                * pool.scale[layer, idx][..., None])
    else:
        rows = pool[layer, idx]
    return rows.reshape(shape)


def _flat_heads(states):
    """``[..., H, hd]`` states as the pools store them: ``[..., H * hd]``."""
    return states.reshape(states.shape[:-2] + (-1,))


def latent_rows(pool, rows):
    """Latent rows ``[..., width]`` as the latent ``pool`` stores them:
    ``[..., 1, lanes]`` in its type, zero lanes after the values (the
    writers' ``[..., H, hd]`` with one "head" as wide as a row, so
    :func:`write_prefill` and :func:`write_decode` scatter them like any
    states)."""
    pad = pool.shape[-1] - rows.shape[-1]
    rows = jnp.pad(rows.astype(pool.dtype),
                   ((0, 0),) * (rows.ndim - 1) + ((0, pad),))
    return rows[..., None, :]


def _attend_blocks(q, read_block, nblk: int, block_size: int, lengths,
                   scale):
    """Shared online-softmax block scan (one query token per row).

    ``q``: [B, H, hd]; ``read_block(j)`` -> ([B, BS, H, hd] K,
    [B, BS, H, hd] V) for logical block ``j``; ``lengths``: [B] valid
    cache entries per row.  Same running (max, sum, acc) statistics as
    ``blockwise_attention`` — f32 stats, ``NEG_INF`` masking — but the
    mask is a length mask, not a causal one: the single query sits at
    position ``lengths-1`` and may see every valid entry.
    """
    f32 = jnp.float32
    b, h, d = q.shape
    m = jnp.full((b, h), NEG_INF, f32)
    l = jnp.zeros((b, h), f32)
    acc = jnp.zeros((b, h, d), f32)
    offs = jnp.arange(block_size)
    for j in range(nblk):
        k_blk, v_blk = read_block(j)
        s = jnp.einsum("bhd,bkhd->bhk", q, k_blk).astype(f32) * scale
        valid = (j * block_size + offs)[None, :] < lengths[:, None]
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(valid[:, None, :], p, 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = (acc * alpha[..., None]
               + jnp.einsum("bhk,bkhd->bhd", p, v_blk.astype(f32)))
        m = m_new
    l = jnp.maximum(l, 1e-30)
    return (acc / l[..., None]).astype(q.dtype)


def paged_attention(q, k_pool, v_pool, layer: int, tables, lengths, *,
                    scale: Optional[float] = None, impl: str = "scan"):
    """One-token-per-request attention over a paged cache.

    ``q``: [B, H, hd] query states; ``k_pool``/``v_pool``: the WHOLE
    pools (plain or :class:`QuantPool`) and ``layer`` the layer to
    read; ``tables``: [B, max_blocks] int32 physical slot
    per logical block (unused entries may hold any valid slot — the
    length mask kills them); ``lengths``: [B] int32 valid cache entries
    (including the current token, which must already be written).
    Returns [B, H, hd].

    ``impl`` selects the read strategy (docs/serving.md "tail-latency
    tuning"):

    * ``"scan"`` — the reference online-softmax block scan (one gather
      + softmax update per block column; the dense [B, L_max] score
      matrix is never materialized).
    * ``"dense"`` — gather every table-addressed block in one shot and
      run a single masked softmax over [B, L_max].  ~10 ops instead of
      ~10·nblk: on CPU (and any thunk-dispatch-bound backend) the scan's
      per-block op chain, not HBM, is the decode bottleneck.  L_max here
      is table capacity — a few hundred positions — so the materialized
      scores are tiny.
    * ``"flash"`` / ``"flash_interpret"`` — the Pallas flash-decode
      kernel (``serve/flash_decode.py``): streams each KV block through
      VMEM once, split-K across blocks for long contexts.  The interpret
      variant runs the same kernel on the CPU backend for tests.
    """
    b, h, d = q.shape
    nblk = tables.shape[1]
    bs = _block_size_of(k_pool)
    scale_ = softmax_scale(d, scale)

    if impl in ("flash", "flash_interpret"):
        from .flash_decode import flash_decode_attention
        with jax.named_scope("attn"):
            return flash_decode_attention(
                q, k_pool, v_pool, layer, tables, lengths, scale=scale_,
                interpret=(impl == "flash_interpret"))

    if impl == "dense":
        f32 = jnp.float32
        k = _gather_blocks(k_pool, layer, tables, (b, nblk * bs, h, d))
        v = _gather_blocks(v_pool, layer, tables, (b, nblk * bs, h, d))
        with jax.named_scope("attn"):
            s = jnp.einsum("bhd,blhd->bhl", q, k).astype(f32) * scale_
            valid = jnp.arange(nblk * bs)[None, :] < lengths[:, None]
            s = jnp.where(valid[:, None, :], s, NEG_INF)
            m = jnp.max(s, axis=-1)
            p = jnp.where(valid[:, None, :], jnp.exp(s - m[..., None]), 0.0)
            l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
            out = jnp.einsum("bhl,blhd->bhd", p, v.astype(f32))
            return (out / l[..., None]).astype(q.dtype)

    if impl != "scan":
        raise MXNetError(f"paged_attention: unknown impl {impl!r}, expected "
                         "'scan', 'dense', 'flash', or 'flash_interpret'")

    def read_block(j):
        slot = tables[:, j]
        return (_gather_blocks(k_pool, layer, slot, (b, bs, h, d)),
                _gather_blocks(v_pool, layer, slot, (b, bs, h, d)))

    with jax.named_scope("attn"):
        return _attend_blocks(q, read_block, nblk, bs, lengths, scale_)


def paged_prefill_attention(q, k_pool, v_pool, layer: int, table_row, start,
                            length, *, scale: Optional[float] = None):
    """Causal attention for one **prefill chunk** over a paged cache.

    ``q``: [C, H, hd] — the chunk's query states at absolute positions
    ``start .. start+C-1``; ``k_pool``/``v_pool``/``layer``: the whole
    pools and the layer to read; ``table_row``: [max_blocks] int32 — one
    request's block table; ``length``: scalar — total valid cache
    entries (the chunk's own K/V must already be written, so position
    ``p`` of the chunk may attend to every cached position ``<= start+p``).
    Returns [C, H, hd].

    Materializes the [C, L_max] score matrix (L_max = table capacity ·
    block size — one request's cache, tiny), dequantizing fp8 pools on
    the gather.  Padded chunk positions (``start+p >= length``) produce
    garbage rows; the engine's sampler only reads the row holding the
    prompt's last token.
    """
    c, h, d = q.shape
    nblk = table_row.shape[0]
    bs = _block_size_of(k_pool)
    scale_ = softmax_scale(d, scale)
    f32 = jnp.float32
    k = _gather_blocks(k_pool, layer, table_row, (nblk * bs, h, d))
    v = _gather_blocks(v_pool, layer, table_row, (nblk * bs, h, d))
    with jax.named_scope("attn"):
        s = jnp.einsum("chd,lhd->chl", q, k).astype(f32) * scale_
        pos = jnp.arange(nblk * bs)
        qpos = start + jnp.arange(c)
        valid = (pos[None, :] <= qpos[:, None]) & (pos[None, :] < length)
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.where(valid[:, None, :], jnp.exp(s - m[..., None]), 0.0)
        l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
        out = jnp.einsum("chl,lhd->chd", p, v.astype(f32))
        return (out / l[..., None]).astype(q.dtype)


def paged_verify_attention(q, k_pool, v_pool, layer: int, tables, lengths, *,
                           scale: Optional[float] = None):
    """Causal attention for one **speculative verify** step: C query
    positions per request over a paged cache.

    ``q``: [B, C, H, hd] — query states at absolute positions
    ``lengths[b] .. lengths[b]+C-1`` (position 0 of the window is the
    request's current last token, 1..C-1 the drafted continuation);
    ``k_pool``/``v_pool``/``layer``: the whole pools and the layer to
    read; ``tables``: [B, max_blocks]; ``lengths``: [B] cache entries valid
    *before* this step.  The window's own K/V must already be written
    (the verify program writes them first, exactly like the decode and
    chunk-prefill twins), so window position ``c`` may attend to every
    cached position ``<= lengths+c``.  Returns [B, C, H, hd].

    Materializes the [B, C, L_max] score matrix in one gather (the
    "dense" decode strategy — C is small, K+1 window positions), with
    the same f32 max/exp/sum masked-softmax math as
    :func:`paged_attention` ``impl="dense"``.  A C=1 window reads the
    cache as the decode step does up to gemm-scheduling ulps (XLA
    contracts the [B, C, ...] einsum differently from the [B, ...]
    one); stream-level greedy byte-identity is what the engine
    guarantees, pinned by tests/test_speculate.py.
    """
    b, c, h, d = q.shape
    nblk = tables.shape[1]
    bs = _block_size_of(k_pool)
    scale_ = softmax_scale(d, scale)
    f32 = jnp.float32
    k = _gather_blocks(k_pool, layer, tables, (b, nblk * bs, h, d))
    v = _gather_blocks(v_pool, layer, tables, (b, nblk * bs, h, d))
    with jax.named_scope("attn"):
        s = jnp.einsum("bchd,blhd->bchl", q, k).astype(f32) * scale_
        pos = jnp.arange(nblk * bs)
        qpos = lengths[:, None] + jnp.arange(c)[None, :]          # [B, C]
        valid = pos[None, None, :] <= qpos[:, :, None]            # [B, C, L]
        s = jnp.where(valid[:, :, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.where(valid[:, :, None, :], jnp.exp(s - m[..., None]), 0.0)
        l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
        out = jnp.einsum("bchl,blhd->bchd", p, v.astype(f32))
        return (out / l[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Latent attention over the paged latent pool (kind ``paged_latent``)
# ---------------------------------------------------------------------------

def _split_kvb(w_kvb, heads: int):
    """The latents' up-projection ``[heads * (nope + v), rank]`` as one
    matrix a head, ``[H, nope + v, rank]``: a head's ``nope`` key rows,
    then its value rows."""
    return w_kvb.reshape(heads, -1, w_kvb.shape[-1])


def latent_absorb(q, w_kvb, nope: int):
    """The decode form's queries: ``q`` [B, H, nope + rope] -> ``[q~ |
    q_r]`` [B, H, rank + rope] with ``q~ = q_n W_kvb[keys]``, so that a
    head's score over a cached row ``[c | k_r]`` is one dot product and
    no key is ever up-projected."""
    w = _split_kvb(w_kvb, q.shape[1])[:, :nope]         # [H, nope, rank]
    qa = jnp.einsum("bhd,hdr->bhr", q[..., :nope], w.astype(q.dtype),
                    preferred_element_type=jnp.float32)
    return jnp.concatenate([qa.astype(q.dtype), q[..., nope:]], axis=-1)


def latent_expand(y, w_kvb, nope: int):
    """``softmax(s) c`` [B, H, rank] -> each head's output [B, H, v]: the
    value half of the up-projection, applied after the attention."""
    w = _split_kvb(w_kvb, y.shape[1])[:, nope:]         # [H, v, rank]
    return jnp.einsum("bhr,hvr->bhv", y, w.astype(y.dtype),
                      preferred_element_type=jnp.float32).astype(y.dtype)


def latent_decode_attention(q, pool, layer: int, tables, lengths, *,
                            rank: int, scale, impl: str = "dense"):
    """One-token-per-request latent attention, absorbed form.

    ``q``: the absorbed queries [B, H, rank + rope]
    (:func:`latent_absorb`); ``pool``: the WHOLE latent pool and
    ``layer`` the layer to read; ``tables`` [B, max_blocks]; ``lengths``
    [B] valid rows, the current one (already written) included.  Returns
    ``softmax(s) c`` [B, H, rank] (:func:`latent_expand` makes the
    heads' outputs of it).

    ``impl``: ``"flash"`` / ``"flash_interpret"`` the Pallas kernel
    (``serve/mla_decode.py``); anything else gathers the tables' blocks
    and runs one masked softmax over ``[B, H, L_max]`` in XLA (the CPU
    engines' reader and the kernel's reference)."""
    b, h, width = q.shape
    scale_ = np.float32(scale)
    if impl in ("flash", "flash_interpret"):
        from .mla_decode import mla_decode_attention
        with jax.named_scope("attn"):
            return mla_decode_attention(
                q, pool, layer, tables, lengths, rank=rank, scale=scale_,
                interpret=(impl == "flash_interpret"))
    f32 = jnp.float32
    nblk, bs = tables.shape[1], pool.shape[2]
    rows = _gather_blocks(pool, layer, tables, (b, nblk * bs, pool.shape[-1]))
    with jax.named_scope("attn"):
        s = jnp.einsum("bhw,blw->bhl", q, rows[..., :width].astype(q.dtype),
                       preferred_element_type=f32) * scale_
        valid = jnp.arange(nblk * bs)[None, :] < lengths[:, None]
        s = jnp.where(valid[:, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.where(valid[:, None, :], jnp.exp(s - m[..., None]), 0.0)
        l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
        out = jnp.einsum("bhl,blr->bhr", p, rows[..., :rank].astype(f32))
        return (out / l[..., None]).astype(q.dtype)


def latent_prefill_attention(q, pool, layer: int, table_row, start, length,
                             w_kvb, *, rank: int, nope: int, scale,
                             ctx_block: int = 512, head_group: int = 32):
    """Causal latent attention for one **prefill chunk**, the
    up-projected form: per-head keys and values are made of the gathered
    latents, ``[k_n | v] = W_kvb c``, and each head attends with ``[q_n
    | q_r] . [k_n | k_r]``.

    ``q``: [C, H, nope + rope] at absolute positions ``start ..
    start+C-1``; ``pool`` / ``layer``: the whole latent pool and the
    layer (the chunk's own rows already written); ``table_row``
    [max_blocks]; ``length``: total valid rows.  Returns [C, H, v].

    The context is walked ``ctx_block`` positions at a time under an
    online softmax, only as far as the chunk's last query can see
    (a loop whose trip count is data, so a first chunk does not pay for
    the table's width), and the heads ``head_group`` at a time: the
    scores alive at once are ``[head_group, C, ctx_block]`` float32,
    never heads x chunk x context."""
    c, h, dq = q.shape
    nblk, (bs, lanes) = table_row.shape[0], pool.shape[2:]
    rope_w = dq - nope
    w = _split_kvb(w_kvb, h).astype(q.dtype)         # [H, nope + v, rank]
    dv = w.shape[1] - nope
    per = max(1, ctx_block // bs)                          # pool blocks a walk
    kb = per * bs
    walks = -(-nblk // per)
    table_p = jnp.pad(table_row, (0, walks * per - nblk))  # the trash block
    g = head_group if h % head_group == 0 else h
    f32 = jnp.float32
    qpos = start + jnp.arange(c)
    seen = jnp.minimum(length, start + c)                  # rows any query sees
    # heads first, the scale folded in: one batched product a walk and
    # group, and no pass over the scores to scale them
    qh = (q.astype(f32) * np.float32(scale)).astype(q.dtype).transpose(1, 0, 2)

    def group(qg, wg):
        """``qg`` [g, C, nope + rope], ``wg`` [g, nope + v, rank]: these
        heads over the context, an online softmax a walk."""
        def walk(j, carry):
            m, l, acc = carry                    # [g, C], [g, C], [g, C, v]
            slots = jax.lax.dynamic_slice(table_p, (j * per,), (per,))
            rows = _gather_blocks(pool, layer, slots, (kb, lanes)).astype(
                q.dtype)
            pos = j * kb + jnp.arange(kb)
            valid = (pos[None, :] <= qpos[:, None]) & (pos[None, :] < length)
            with jax.named_scope("attn"):
                kv = jnp.einsum("lr,gdr->gld", rows[:, :rank], wg,
                                preferred_element_type=f32).astype(q.dtype)
                keys = jnp.concatenate(
                    [kv[..., :nope], jnp.broadcast_to(
                        rows[None, :, rank:rank + rope_w], (g, kb, rope_w))],
                    axis=-1)                                  # [g, kb, dq]
                s = jnp.einsum("gcd,gld->gcl", qg, keys,
                               preferred_element_type=f32)
                s = jnp.where(valid[None], s, NEG_INF)
                # every query sees position 0, so the maximum is a score
                # from the first walk on and a masked entry's exp is 0
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new[..., None])
                l = l * alpha + jnp.sum(p, axis=-1)
                acc = acc * alpha[..., None] + jnp.einsum(
                    "gcl,glv->gcv", p.astype(q.dtype), kv[..., nope:],
                    preferred_element_type=f32)
            return m_new, l, acc

        init = (jnp.full((g, c), NEG_INF, f32), jnp.zeros((g, c), f32),
                jnp.zeros((g, c, dv), f32))
        _, l, acc = jax.lax.fori_loop(0, -(-seen // kb), walk, init)
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jnp.concatenate([group(qh[i:i + g], w[i:i + g])
                           for i in range(0, h, g)], axis=0)   # [H, C, v]
    return out.transpose(1, 0, 2).astype(q.dtype)


# ---------------------------------------------------------------------------
# Grouped-query softmax attention, bounded by a window (kinds paged_kv and
# paged_window of a described model)
# ---------------------------------------------------------------------------

def _window_floor(length, window: int):
    """The first position a query at ``length - 1`` sees."""
    return jnp.maximum(length - window, 0) if window else jnp.zeros_like(
        length)


def gqa_prefill_attention(q, k_pool, v_pool, layer: int, table_row, start,
                          length, *, scale, window: int = 0, ring: int = 0,
                          ctx_block: int = 512):
    """Causal grouped-query attention for one **prefill chunk**:
    ``q`` [C, H, hd] at absolute positions ``start .. start+C-1``; the
    pools store ``kv_heads * hd`` lanes a position (query head ``i``
    reads key/value head ``i // (H / kv_heads)``); ``table_row`` a
    global table [max_blocks], or with ``ring`` a window table (position
    ``p`` in column ``(p // bs) % ring``); ``length`` the valid
    positions, the chunk's own already written.  With ``window`` a
    query at ``p`` sees positions ``p - window < j <= p``.  Returns [C,
    H, hd].

    The context is walked ``ctx_block`` positions at a time under an
    online softmax, from the window's first block (or 0) as far as the
    chunk's last query sees (a loop whose bounds are data), so the
    scores alive at once are ``[H, C, ctx_block]`` float32."""
    c, h, hd = q.shape
    nblk, bs = table_row.shape[0], _block_size_of(k_pool)
    kv = k_pool.shape[-1] // hd
    g = h // kv
    per = max(1, ctx_block // bs)                          # pool blocks a walk
    kb = per * bs
    if not ring:
        # the walk reads whole walks of columns: pad with the trash block
        table_row = jnp.pad(table_row, (0, -(-nblk // per) * per - nblk))
    f32 = jnp.float32
    qpos = start + jnp.arange(c)
    seen = jnp.minimum(length, start + c)              # rows any query sees
    lo = _window_floor(start + 1, window)                  # the first query's
    # [kv, g, C, hd], the scale folded in
    qg = (q.astype(f32) * np.float32(scale)).astype(q.dtype).reshape(
        c, kv, g, hd).transpose(1, 2, 0, 3)

    def walk(j, carry):
        m, l, acc = carry                 # [kv, g, C] twice, [kv, g, C, hd]
        cols = j * per + jnp.arange(per)
        slots = jnp.take(table_row, cols % ring if ring else cols)
        k = _gather_blocks(k_pool, layer, slots, (kb, kv, hd)).astype(q.dtype)
        v = _gather_blocks(v_pool, layer, slots, (kb, kv, hd)).astype(q.dtype)
        pos = j * kb + jnp.arange(kb)
        valid = (pos[None, :] <= qpos[:, None]) & (pos[None, :] < length)
        if window:
            valid &= pos[None, :] > qpos[:, None] - window
        with jax.named_scope("attn"):
            s = jnp.einsum("kgcd,lkd->kgcl", qg, k, preferred_element_type=f32)
            s = jnp.where(valid, s, NEG_INF)
            # a query whose window opens in a later walk folds nothing
            # in before it: its masked probabilities are zeros
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "kgcl,lkd->kgcd", p.astype(q.dtype), v,
                preferred_element_type=f32)
        return m_new, l, acc

    init = (jnp.full((kv, g, c), NEG_INF, f32), jnp.zeros((kv, g, c), f32),
            jnp.zeros((kv, g, c, hd), f32))
    _, l, acc = jax.lax.fori_loop(lo // kb, -(-seen // kb), walk, init)
    out = acc / jnp.maximum(l, 1e-30)[..., None]           # [kv, g, C, hd]
    return out.transpose(2, 0, 1, 3).reshape(c, h, hd).astype(q.dtype)


def _ring_positions(nblk: int, lengths, ring: int, block_size: int):
    """The absolute position of every slot of a row's window table
    [B, ring * block_size]: column ``r`` holds the latest logical block
    ``l <= (length - 1) // block_size`` with ``l % ring == r`` (a column
    no write reached yet gets a negative, never-valid position)."""
    last = jnp.maximum(lengths - 1, 0) // block_size              # [B]
    r = jnp.arange(nblk)
    logical = last[:, None] - (last[:, None] - r[None, :]) % ring
    return (logical[..., None] * block_size
            + jnp.arange(block_size)).reshape(lengths.shape[0], -1)


def gqa_decode_attention(q, k_pool, v_pool, layer: int, tables, lengths, *,
                         scale, window: int = 0, ring: int = 0,
                         impl: str = "dense"):
    """One-token-per-row grouped-query attention over a paged cache,
    bounded by ``window`` (0: the whole prefix).  ``q`` [B, H, hd];
    the WHOLE pools (``kv_heads * hd`` lanes a position) and the layer;
    ``tables`` [B, columns], a window table with ``ring``; ``lengths``
    [B] valid positions, the current one (already written) included, 0
    for a row that attends nothing.  Returns [B, H, hd].

    ``impl``: ``"flash"`` / ``"flash_interpret"`` the Pallas kernel
    ``mxtpu_gqa_decode`` (``serve/gqa_decode.py``), whose walk over a
    row's blocks starts at the window's first block; anything else
    gathers the table's blocks and runs one masked softmax in XLA (the
    CPU engines' reader and the kernel's reference)."""
    b, h, hd = q.shape
    scale = np.float32(scale)
    if impl in ("flash", "flash_interpret"):
        from .gqa_decode import gqa_decode
        with jax.named_scope("attn"):
            return gqa_decode(q, k_pool, v_pool, layer, tables, lengths,
                              scale=scale, window=window, ring=ring,
                              interpret=impl == "flash_interpret")
    f32 = jnp.float32
    nblk, bs = tables.shape[1], _block_size_of(k_pool)
    kv = k_pool.shape[-1] // hd
    k = _gather_blocks(k_pool, layer, tables, (b, nblk * bs, kv, hd))
    v = _gather_blocks(v_pool, layer, tables, (b, nblk * bs, kv, hd))
    if ring:
        pos = _ring_positions(nblk, lengths, ring, bs)
    else:
        pos = jnp.broadcast_to(jnp.arange(nblk * bs), (b, nblk * bs))
    valid = ((pos >= _window_floor(lengths, window)[:, None])
             & (pos < lengths[:, None]))                          # [B, L]
    with jax.named_scope("attn"):
        qg = q.reshape(b, kv, h // kv, hd)
        s = jnp.einsum("bkgd,blkd->bkgl", qg, k.astype(q.dtype),
                       preferred_element_type=f32) * scale
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.where(valid[:, None, None, :], jnp.exp(s - m[..., None]), 0.0)
        l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
        out = jnp.einsum("bkgl,blkd->bkgd", p, v.astype(f32))
        return (out / l[..., None]).reshape(b, h, hd).astype(q.dtype)


def dense_attention(q, k_buf, v_buf, lengths, *, block_size: int,
                    scale: Optional[float] = None):
    """The dense (non-paged) counterpart: same block scan, but K/V come
    from contiguous per-request buffers ``[B, L_pad, H, hd]``
    (``L_pad`` a multiple of ``block_size``).  Used by the parity tests:
    paged vs dense must agree bitwise because the only difference is a
    gather of identical values at identical shapes."""
    b, lpad, h, d = k_buf.shape
    if lpad % block_size:
        raise MXNetError(f"dense cache length {lpad} not a multiple of "
                         f"block {block_size}")
    nblk = lpad // block_size
    scale_ = softmax_scale(d, scale)
    kb = k_buf.reshape(b, nblk, block_size, h, d)
    vb = v_buf.reshape(b, nblk, block_size, h, d)

    def read_block(j):
        return kb[:, j], vb[:, j]

    return _attend_blocks(q, read_block, nblk, block_size, lengths, scale_)


@_scoped("kv_write")
def write_prefill(pool, layer: int, states, table_row, length, start=0,
                  ring: int = 0):
    """Scatter a prompt's (or prompt chunk's) K or V states into its
    table's slots.

    ``pool``: the whole pool (plain or :class:`QuantPool`); ``states``:
    [L_pad, H, hd] (bucket- or chunk-padded; stored flattened to
    ``H * hd``, and nothing else about them changes); ``table_row``:
    [max_blocks] int32; ``length``: scalar total valid positions;
    ``start``: absolute position of ``states[0]`` (chunked prefill
    writes chunk *i* with ``start = i * chunk``).  Positions
    ``>= length`` land in the trash block.  ``ring`` (a window table's
    width, :func:`ring_width`): position ``p`` goes to column ``(p //
    block_size) % ring``.  Returns the updated pool
    (functional; donate the input).  Quantized pools quantize each
    position row (fp8 payload + f32 scale) and scatter both with the
    same indices.
    """
    lpad = states.shape[0]
    bs = _block_size_of(pool)
    pos = start + jnp.arange(lpad)
    logical = pos // bs
    if ring:
        logical = logical % ring
    else:
        # bucket L_pad may exceed table capacity * BS for short prompts;
        # clamp the logical index — those positions are >= length anyway.
        logical = jnp.minimum(logical, table_row.shape[0] - 1)
    slot = jnp.where(pos < length, jnp.take(table_row, logical),
                     TRASH_BLOCK)
    off = pos % bs
    states = _flat_heads(states)
    if is_quantized(pool):
        q, s = quantmod.rowwise_quantize(states, KV_FP8_FORMAT)
        return QuantPool(pool.payload.at[layer, slot, off].set(q),
                         pool.scale.at[layer, slot, off].set(s))
    return pool.at[layer, slot, off].set(states)


@_scoped("kv_write")
def write_decode(pool, layer: int, states, slots, offsets, active):
    """Scatter one decode step's K or V states, one position per row.

    ``states``: [B, H, hd]; ``slots``: [B] physical block per row;
    ``offsets``: [B] position within the block; ``active``: [B] bool —
    inactive rows write to the trash block.  Returns the updated pool.
    """
    slot = jnp.where(active, slots, TRASH_BLOCK)
    states = _flat_heads(states)
    if is_quantized(pool):
        q, s = quantmod.rowwise_quantize(states, KV_FP8_FORMAT)
        return QuantPool(pool.payload.at[layer, slot, offsets].set(q),
                         pool.scale.at[layer, slot, offsets].set(s))
    return pool.at[layer, slot, offsets].set(states)


@_scoped("kv_write")
def write_spec(pool, layer: int, states, slots, offsets):
    """Scatter one speculative-verify window's K or V states: C
    positions per row.

    ``states``: [B, C, H, hd]; ``slots``/``offsets``: [B, C] physical
    block and in-block position per window entry.  The caller masks
    dead entries (inactive rows, positions past the row's live draft
    count) by pointing their slot at the trash block — the scatter
    itself is unconditional, like :func:`write_decode`.  Quantized
    pools quantize each position row independently (flattened to
    ``[B*C, H*hd]`` so a position's fp8 payload+scale is a pure
    function of its states, independent of the window shape — the
    byte-identity contract of speculative decode depends on it).
    """
    states = _flat_heads(states)
    if is_quantized(pool):
        b, c = states.shape[:2]
        q, s = quantmod.rowwise_quantize(
            states.reshape((b * c,) + states.shape[2:]), KV_FP8_FORMAT)
        return QuantPool(
            pool.payload.at[layer, slots, offsets].set(
                q.reshape(states.shape)),
            pool.scale.at[layer, slots, offsets].set(s.reshape(b, c)))
    return pool.at[layer, slots, offsets].set(states)


def scrub_positions(pool, slots, offsets):
    """Zero individual cache positions — payload and scales — across
    every layer: the rejection path of speculative decode.  ``slots``/
    ``offsets``: [B, C]; entries the caller wants to keep point at the
    trash block (scrubbing trash is free).  A rejected draft's K/V must
    not survive at a position the block cursor rolled back over: the
    next append overwrites it, but until then masked attention lanes
    still read it (multiply-by-zero — the PR-12 NaN lesson), and the
    rollback contract is that truncated positions hold no stale state.
    """
    return jax.tree_util.tree_map(
        lambda a: a.at[:, slots, offsets].set(0), pool)


def scrub_blocks(pool, blocks):
    """Zero the given physical blocks (payload and scales).  Called
    when a request's cached K/V may be non-finite (NaN-poisoned step,
    caught by the engine's finite guard): blocks must return to the
    free pool finite, because attention masks invalid lanes by
    *multiplying by zero* — and ``0 * NaN`` is NaN, so a non-finite
    residue would leak into whichever request reuses the block."""
    if not blocks:
        return pool
    idx = jnp.asarray(sorted(set(int(b) for b in blocks)), jnp.int32)
    return jax.tree_util.tree_map(lambda a: a.at[:, idx].set(0), pool)


def compact_pool(pool, mapping: Dict[int, int]):
    """Apply a :meth:`BlockAllocator.defrag` relocation map to a pool:
    copy each moved slot's contents to its new physical index.  Values
    are moved, never transformed, so post-defrag attention output is
    bitwise identical (gather of the same values) — for quantized pools
    payload and scales relocate together."""
    if not mapping:
        return pool
    src = jnp.asarray(sorted(mapping), jnp.int32)
    dst = jnp.asarray([mapping[int(s)] for s in sorted(mapping)], jnp.int32)
    return jax.tree_util.tree_map(
        lambda a: a.at[:, dst].set(a[:, src]), pool)
