"""KV cache memory manager for the serve engine.

`PagedKVCache` is THE engine's cache (`serve/engine.py`): a fixed pool
of ``(num_blocks, block_size, kv_heads, head_dim)`` K/V blocks per
layer plus a per-slot block table mapping logical blocks to physical
ones. Blocks are allocated ON WRITE (as prefill chunks land and as
decode crosses block boundaries) and freed at retire, so cache memory
per request tracks LIVE tokens — not ``slots x max_seq_len`` the way
a dense per-slot layout would. Entries equal to ``num_blocks`` mark
unallocated logical blocks; the paged attention path
(`models/transformer.py::_decode_paged`) turns writes through them
into out-of-bounds scatter drops, which is how parked lanes and
padded chunks stay harmless. The pool is exhaustible by design: a
failed `ensure_blocks` is the engine's backpressure/preemption
signal. `quantized=True` stores K/V as INT8 with per-(token,
kv-head) f32 scales (`ops/quant.py`) — ~(4 / (1 + 4/head_dim))x
more blocks at fixed pool bytes, quantize-on-scatter in the paged
write, dequant inside `ops.gather_paged_kv` so attention math stays
full precision.

THREE FAMILIES of table (`serve/kinds.py` tells them, and which kind of
layer rides which): the refcounted BLOCKS described above, for the layers
that keep every token (K and V heads, or a latent row: a block id names the
same tokens in each of them); a WINDOW pool of its own (`window_num_blocks`
blocks, sized from the slots, the window, the prefill chunk and the block
size: a row never holds more than `window_blocks_per_slot`) under a second
table of the same logical shape, whose blocks wholly behind `position -
window` go back to the window free list WHILE the request runs
(`ensure_blocks(..., first_pos=)`: the entry turns invalid, and the
attention paths never read before a row's first attended block); and the
STATE blocks, one a request from `allocate()` to `free()` whatever its
length, the same index in every layer that keeps one, under a (slots, 1)
table. An invalid entry drops the write in every family, and a state block
is never cleared: its mixer reads zero for a row whose first token stands
at position 0, so a block taken over from a retired or preempted request
starts clean. The manager allocates, frees and counts without naming a
kind or a leaf: what a layer's pool looks like is its kind's record
(`Kind.pool`, from the leaves its mixer names:
`models/transformer.py::cache_leaves`), `tables()` hands the programs one
table a kind the model has, in `cfg.cache_kinds`' order, and `free`,
`bytes_live` and the live-block counts account every family. A model of
full layers alone gets exactly the single tree, table and accounting.

Physical blocks are REFCOUNTED (ISSUE 12): `attach_prefix` lets a
slot reference blocks another request already filled (the prefix
cache, `serve/prefix.py`), `free()` DECREMENTS instead of releasing
(a block returns to the reusable set only when its last reference
drops), and writes go copy-on-write — `cow_block(slot, pos)` copies
a block (pool K/V AND the int8 scale planes, one jitted
gather/scatter per layer tree) before the slot may write into it
while it is shared (refcount > 1) or pinned by a prefix-index entry.
Shared physical blocks are counted ONCE everywhere (`live_blocks`,
`bytes_live`, `pool_utilization`); `bytes_deduplicated` is the pool
memory sharing saves vs a no-sharing layout. Blocks whose refcount
hits zero while a prefix-index entry still names them move to a
CACHED free list: they stay reclaimable (counted in `free_blocks`,
handed out LRU after the plain free list drains, invalidating their
index entry through `evict_hook`) but keep their content until then,
which is what lets a retired request's prompt prefix serve later
identical prompts for free.

The manager keeps per-slot lengths host-side and replaces its device
tree functionally — callers own exactly one live version.
"""

from __future__ import annotations

import functools
from collections import Counter, OrderedDict
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

__all__ = [
    "PagedKVCache",
    "init_paged_cache",
]


@functools.lru_cache(maxsize=8)
def _copy_block_fn():
    """Jitted whole-block pool copy — the copy-on-write data mover.

    Copies physical block `src` onto physical block `dst` across EVERY
    pool leaf (K, V, and — quantized pools — the `k_scale`/`v_scale`
    planes ride the same tree_map, so a CoW'd int8 block needs no
    requantization: its per-(token, kv-head) scales copy bit-for-bit
    alongside the payload). The tree is DONATED, matching the serve
    programs' in-place-update discipline; `src`/`dst` ride in as int32
    scalars so the program compiles once per tree shape. Under a TP
    mesh the pool leaves carry KV-head shardings and GSPMD keeps the
    copy local per shard (block axis is unsharded)."""
    import jax

    def copy(tree, src, dst):
        def leaf(buf):
            if buf.ndim == 0:
                return buf
            return buf.at[dst].set(buf[src])

        return jax.tree_util.tree_map(leaf, tree)

    return jax.jit(copy, donate_argnums=(0,))


@functools.lru_cache(maxsize=8)
def _import_blocks_fn():
    """Jitted migration scatter — lands an `export_blocks` payload onto
    the destination pool's physical blocks (the disagg receive path).

    Same donated in-place-update discipline as `_copy_block_fn`; the
    payload tree rides alongside the pool tree (K, V, and quantized
    pools' scale planes all in one tree_map), so int8 bytes and their
    scales scatter together with no requantization. Compiles once per
    (pool shapes, payload block count) — block counts are bounded by
    `blocks_per_seq`, so the executable set stays small."""
    import jax

    def imp(tree, payload, idx):
        def leaf(buf, pay):
            if buf.ndim == 0:
                return buf
            return buf.at[idx].set(pay.astype(buf.dtype))

        return jax.tree_util.tree_map(leaf, tree, payload)

    return jax.jit(imp, donate_argnums=(0,))


def pool_avals(cfg, blocks: Dict[str, Optional[int]], block_size: int,
               quantized: bool = False) -> Dict[str, tuple]:
    """kind -> (its mixer's name, leaf -> `ShapeDtypeStruct` of ONE layer's
    subtree as the pool holds it), for the kinds `cfg`'s layers are of: the
    kind's record says what (`serve/kinds.py::Kind.pool`, over the leaves
    the mixer names), of as many blocks as `blocks` gives the family of
    table the kind rides."""
    from ..models.transformer import cache_leaves
    from .kinds import FAMILIES, KINDS, kinds_of

    records = kinds_of(cfg)
    for family in FAMILIES:
        if blocks[family] is None and any(k.table == family for k in records):
            riders = [k.name for k in KINDS.values() if k.table == family]
            raise ValueError(
                f"a model with {' or '.join(riders)} layers needs {family}_blocks"
            )
    pools = {}
    for kind in records:
        mixer, _, leaves = cache_leaves(cfg, kind.name)
        pools[kind.name] = (
            mixer, kind.pool(leaves, blocks[kind.table], block_size, quantized)
        )
    return pools


def _empty_tree(cfg, pools):
    """The pool tree of zeros: per layer, its kind's subtree of `pools`."""
    import jax.numpy as jnp

    from ..models.transformer import layers_of

    tree = {}
    for i, kind in enumerate(layers_of(cfg)):
        mixer, avals = pools[kind]
        tree[f"layers_{i}"] = {mixer: {
            leaf: jnp.zeros(aval.shape, aval.dtype) for leaf, aval in avals.items()
        }}
    return tree


def _block_bytes(avals) -> int:
    """Bytes ONE block pins in pools of these shapes (blocks lead)."""
    return sum(
        int(np.prod(aval.shape[1:])) * np.dtype(aval.dtype).itemsize
        for aval in avals
    )


def init_paged_cache(model, num_blocks: int, block_size: int,
                     quantized: bool = False,
                     window_blocks: Optional[int] = None,
                     state_blocks: Optional[int] = None):
    """Empty paged pool tree for `model`: per layer, under its mixer's
    name, what its kind keeps (`pool_avals`): a full layer one (num_blocks,
    block_size, kv_heads, head_dim) K and V (as `ops.pool_kv_shape` holds
    the heads), a window layer the same of `window_blocks` blocks, a latent
    layer ONE (num_blocks, block_size, latent_width) pool, and a layer
    whose mixer keeps a state `state_blocks` blocks of the leaves the mixer
    names. Mirrors `models.generate.init_cache`'s structure minus the
    scalar "index" leaf (a shared pool has no per-row cursor).

    `quantized=True` switches the pool to INT8 K/V plus per-(block
    slot, kv-head) f32 scale planes `k_scale`/`v_scale` of shape
    (num_blocks, block_size, kv_heads) — one max-abs scale per stored
    token vector (`ops/quant.py::quantize_kv`), the granularity that
    lets quantize-on-scatter land a token in a shared block without
    requantizing the block's earlier tokens. The paged attention path
    detects the scale planes and dequantizes inside
    `ops.gather_paged_kv`, so the attention math stays cfg.dtype."""
    return _empty_tree(model.cfg, pool_avals(
        model.cfg,
        {"blocks": num_blocks, "window": window_blocks, "state": state_blocks},
        block_size, quantized,
    ))


class PagedKVCache:
    """Block-pool KV cache: slot bookkeeping + allocate-on-write blocks.

    `tree` is the live pool tree (one (num_blocks, block_size, KV, Dh)
    K/V pool per layer, shared by every slot); `block_tables` is the
    HOST (slots, nb) int32 table the jitted programs consume per call
    (entries == num_blocks mark unallocated logical blocks — tiny, and
    it changes only at admission/growth/retire, so shipping it per step
    is cheaper than donated-device choreography); `lengths` mirrors
    per-slot depth for introspection. Blocks return to the free list at
    `free()` (retire/preempt) in FIFO reuse order.

    Refcounts + copy-on-write (ISSUE 12): every physical block carries
    a reference count. `ensure_blocks` hands out refcount-1 blocks;
    `attach_prefix` lets a slot adopt already-filled blocks (prefix
    sharing — refcount incremented, content untouched); `free()`
    DECREMENTS, so a shared block outlives any single holder and is
    counted once in every byte/utilization figure. A slot about to
    write into a block that is shared (refcount > 1) or pinned by a
    prefix-index entry must call `cow_block` first: the block is copied
    to a fresh one (K/V and scale planes), the slot's table is
    repointed, and the original keeps serving its other holders — so
    partial-boundary divergence costs exactly one block copy. Blocks
    whose refcount hits 0 while still named by a prefix index park on a
    CACHED free list: reclaimable (LRU, after the plain free list,
    invalidating their index entry via `evict_hook`) but content-
    preserving until actually reused.
    """

    def __init__(
        self,
        model,
        slots: int,
        num_blocks: Optional[int] = None,
        block_size: int = 16,
        quantized: bool = False,
        chunk_tokens: Optional[int] = None,
    ):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        from ..models.transformer import layers_of
        from .kinds import KINDS, kinds_of

        cfg = model.cfg
        M = cfg.max_seq_len
        # the kinds of state the model's layers keep, in the order the
        # programs take their tables, and the family of table each rides
        self.records = kinds_of(cfg)
        self.kinds = tuple(kind.name for kind in self.records)
        self._families = tuple(kind.table for kind in self.records)
        # layers of each kind (`full_layers`, `window_layers`, ...: 0 for a
        # kind the model has none of) and of those that ride each family
        count = Counter(layers_of(cfg))
        for name in KINDS:
            setattr(self, f"{name}_layers", count[name])
        self.layers = {name: count[name] for name in self.kinds}
        riding = Counter()
        for kind in self.records:
            riding[kind.table] += count[kind.name]
        self.state_layers = riding["state"]
        self.model = model
        self.slots = slots
        self.block_size = block_size
        self.quantized = quantized
        self.blocks_per_seq = -(-M // block_size)  # nb: ceil(M / bs)
        if num_blocks is None:
            # dense-equivalent capacity: every slot can hold max_seq_len.
            # Size it DOWN (bench/production) to cap memory at expected
            # live tokens and let backpressure/preemption absorb bursts.
            num_blocks = slots * self.blocks_per_seq
        if num_blocks < self.blocks_per_seq:
            raise ValueError(
                f"num_blocks ({num_blocks}) cannot hold even one "
                f"max-length request ({self.blocks_per_seq} blocks)"
            )
        self.num_blocks = num_blocks
        self.invalid_block = num_blocks  # OOB sentinel the paged path drops
        # the window kind: a row holds the blocks of the `window - 1` keys
        # behind its next write and of the longest write (`chunk_tokens`;
        # None = a whole prompt in one program), a partial block at each
        # end: one more block than the longest span a gather takes
        self.window = cfg.window if riding["window"] else None
        self.window_blocks_per_slot = self.window_num_blocks = 0
        if riding["window"]:
            span = self.window + min(chunk_tokens or M, M)
            self.window_blocks_per_slot = min(
                -(-span // block_size) + 2, self.blocks_per_seq
            )
            self.window_num_blocks = slots * self.window_blocks_per_slot
        self.window_invalid_block = self.window_num_blocks
        # the state kind: one block a slot, held from allocate() to free()
        self.state_num_blocks = slots if self.state_layers else 0
        self.state_invalid_block = self.state_num_blocks
        self.state_table = np.full((slots, 1), self.state_invalid_block, np.int32)
        self._state_free: List[int] = list(range(self.state_num_blocks))
        pools = pool_avals(cfg, {
            "blocks": num_blocks, "window": self.window_num_blocks or None,
            "state": self.state_num_blocks or None,
        }, block_size, quantized)
        self.tree = _empty_tree(cfg, pools)
        # per kind, one layer's pool (leaf -> `ShapeDtypeStruct`): for
        # callers that ask about a pool without naming the tree's keys
        self.avals = {name: avals for name, (_, avals) in pools.items()}
        # per gauge of `/serve` (`Kind.gauge`): the family whose live blocks
        # it counts, and the bytes one of them pins across its layers
        self._gauges: Dict[str, tuple] = {}
        for kind in self.records:
            _, had = self._gauges.get(kind.gauge, (None, 0))
            self._gauges[kind.gauge] = (kind.table, had + self._kind_bytes(kind))
        self.window_tables = np.full(
            (slots, self.blocks_per_seq), self.window_invalid_block, np.int32
        )
        self._window_free: List[int] = list(range(self.window_num_blocks))
        # per slot: logical block -> physical window block, in order
        self._window_slot_blocks: List[Dict[int, int]] = [
            {} for _ in range(slots)
        ]
        self.window_blocks_recycled = 0  # freed while their request ran
        self.block_tables = np.full(
            (slots, self.blocks_per_seq), self.invalid_block, np.int32
        )
        self.lengths = np.zeros((slots,), np.int32)
        self._in_use = np.zeros((slots,), bool)
        self._free_slots: List[int] = list(range(slots))
        self._free_blocks: List[int] = list(range(num_blocks))
        self._slot_blocks: List[List[int]] = [[] for _ in range(slots)]
        # prefix-sharing plane: per-block refcounts, the set of blocks a
        # prefix index currently names, the refcount-0-but-still-indexed
        # cached list (LRU reclaim order), the index's invalidation hook
        # (PrefixIndex wires itself in), and the CoW copy counter
        self._refcount = np.zeros((num_blocks,), np.int32)
        self._indexed: set = set()
        self._cached_blocks: "OrderedDict[int, None]" = OrderedDict()
        self.evict_hook: Optional[Callable[[int], None]] = None
        self.cow_copies = 0

    # -- slot lifecycle ----------------------------------------------------
    def allocate(self) -> Optional[int]:
        """A free slot index (no K/V blocks yet — those come on write;
        with layers that keep a state, its one state block), or None when
        every slot is taken."""
        if not self._free_slots:
            return None
        s = self._free_slots.pop(0)
        self._in_use[s] = True
        if self.state_layers:  # as many state blocks as slots: never dry
            self.state_table[s, 0] = self._state_free.pop(0)
        return s

    def free(self, slot: int) -> int:
        """Retire a slot: DECREMENT each of its blocks' refcounts and
        invalidate its table row. A block returns to the reusable pool
        only when its last reference drops (shared prefix blocks stay
        live for their other holders — the class-aware eviction path
        therefore frees a shared-prefix victim without touching the
        prefix). Returns the number of blocks whose refcount hit zero
        (= blocks actually reclaimable again)."""
        if not self._in_use[slot]:
            raise ValueError(f"slot {slot} is not allocated")
        n = 0
        for b in self._slot_blocks[slot]:
            n += self._decref(b)
        self._slot_blocks[slot] = []
        self.block_tables[slot, :] = self.invalid_block
        self._window_free.extend(self._window_slot_blocks[slot].values())
        self._window_slot_blocks[slot] = {}
        self.window_tables[slot, :] = self.window_invalid_block
        if self.state_layers:
            self._state_free.append(int(self.state_table[slot, 0]))
            self.state_table[slot, 0] = self.state_invalid_block
        self._in_use[slot] = False
        self.lengths[slot] = 0
        self._free_slots.append(slot)
        return n

    def reset(self) -> None:
        """Free every slot and block. Device pool buffers are NOT
        cleared — unallocated logical blocks are unreachable through the
        tables, and a block's garbage is masked until overwritten."""
        for s in range(self.slots):
            if self._in_use[s]:
                self.free(s)

    # -- block plane -------------------------------------------------------
    def blocks_for(self, tokens: int) -> int:
        """Blocks needed to hold `tokens` positions."""
        return -(-tokens // self.block_size)

    def ensure_blocks(
        self, slot: int, upto_pos: int, first_pos: Optional[int] = None
    ) -> bool:
        """Grow `slot`'s table so position `upto_pos` is writable
        (allocate-on-write). All-or-nothing: returns False — allocating
        NOTHING — when the reclaimable set (plain free list + cached
        prefix blocks) can't cover the growth; the engine turns that
        into backpressure or preemption.

        With window layers the window table grows to `upto_pos` too;
        `first_pos` is the position of the first query of the write this
        call prepares (the chunk's start, the decode token's position):
        window blocks wholly behind `first_pos - window` are recycled
        first. The window pool is sized so that this never fails."""
        if not self._in_use[slot]:
            raise ValueError(f"slot {slot} is not allocated")
        if not 0 <= upto_pos < self.blocks_per_seq * self.block_size:
            raise ValueError(
                f"position {upto_pos} outside the slot's "
                f"{self.blocks_per_seq}-block table"
            )
        have = len(self._slot_blocks[slot])
        need = upto_pos // self.block_size + 1 - have
        if need > self.free_blocks:
            return False
        for j in range(have, have + need):
            b = self._take_block()
            self._refcount[b] = 1
            self._slot_blocks[slot].append(b)
            self.block_tables[slot, j] = b
        if self.window_num_blocks:
            self._ensure_window(slot, upto_pos, first_pos)
        return True

    def _ensure_window(self, slot: int, upto_pos: int, first_pos) -> None:
        """The window kind's half of `ensure_blocks`: recycle, then grow."""
        bs, held = self.block_size, self._window_slot_blocks[slot]
        # the earliest key any query from `first_pos` on attends is
        # first_pos - window + 1: blocks wholly before it are not needed
        keep_from = 0
        if first_pos is not None:
            keep_from = max(first_pos - self.window + 1, 0) // bs
        for j in [j for j in held if j < keep_from]:
            self._window_free.append(held.pop(j))
            self.window_tables[slot, j] = self.window_invalid_block
            self.window_blocks_recycled += 1
        for j in range(max(max(held, default=-1) + 1, keep_from), upto_pos // bs + 1):
            if len(held) >= self.window_blocks_per_slot or not self._window_free:
                raise RuntimeError(
                    f"slot {slot} would hold more than "
                    f"{self.window_blocks_per_slot} window blocks: a write "
                    "longer than the chunk the pool was sized for"
                )
            held[j] = b = self._window_free.pop(0)
            self.window_tables[slot, j] = b

    def tables(self, rows=slice(None), parked=()):
        """The block tables the programs take for the given slots: the
        (n, nb) table, or where the model's layers keep more than one
        kind of state the tuple of one table a kind, each its family's
        (`serve/kinds.py`: the (n, nb) block table, the (n, nb) window
        table, the (n, 1) state table), in `cfg.cache_kinds`' order.
        `parked` slots' rows are handed
        over all-invalid. Always COPIES: the engine goes on growing and
        freeing rows while the program it handed a table to is still
        queued, and a program may read its host arguments late (the CPU
        client aliases them, a device client copies them behind the
        dispatch)."""
        have = {
            "blocks": (self.block_tables, self.invalid_block),
            "window": (self.window_tables, self.window_invalid_block),
            "state": (self.state_table, self.state_invalid_block),
        }
        out = [have[family][0][rows].copy() for family in self._families]
        for t, family in zip(out, self._families):
            t[list(parked)] = have[family][1]
        return tuple(out) if len(out) > 1 else out[0]

    # -- refcount plumbing -------------------------------------------------
    def _take_block(self) -> int:
        """Pop a reusable physical block: plain free list first (FIFO —
        the PR 6 reuse order, unchanged when no prefix index runs),
        then the CACHED list oldest-freed-first, invalidating the
        evicted block's prefix-index entry (and, through the hook, its
        whole subtree — a child prefix is meaningless once its parent's
        content is gone). Caller sets the refcount."""
        if self._free_blocks:
            return self._free_blocks.pop(0)
        b, _ = self._cached_blocks.popitem(last=False)
        if self.evict_hook is not None:
            self.evict_hook(b)
        # the hook deindexed b's subtree; b itself was already popped
        self._indexed.discard(b)
        return b

    def _ref_block(self, b: int) -> None:
        """Add one reference to `b`; a reclaimable (refcount-0) block
        leaves the free set again — the cached list for indexed blocks
        (the only attach source in production), the plain free list
        defensively."""
        if self._refcount[b] == 0:
            if b in self._cached_blocks:
                del self._cached_blocks[b]
            elif b in self._free_blocks:
                self._free_blocks.remove(b)
        self._refcount[b] += 1

    def _decref(self, b: int) -> int:
        """Drop one reference; returns 1 when the block became
        reclaimable (refcount hit 0 — parked cached when a prefix index
        still names it, plain free otherwise)."""
        self._refcount[b] -= 1
        if self._refcount[b] > 0:
            return 0
        if b in self._indexed:
            self._cached_blocks[b] = None
        else:
            self._free_blocks.append(b)
        return 1

    def _deindex(self, b: int) -> None:
        """Prefix-index callback: entry naming `b` is gone. A cached
        block demotes to the plain free list; a still-referenced block
        just loses its write protection."""
        self._indexed.discard(b)
        if b in self._cached_blocks:
            del self._cached_blocks[b]
            self._free_blocks.append(b)

    def mark_indexed(self, b: int) -> None:
        """Prefix-index callback: an index node now names `b` — its
        content must survive refcount 0 (cached, reclaim-last) and any
        write into it must copy first (`cow_block`)."""
        self._indexed.add(b)

    def refcount(self, b: int) -> int:
        return int(self._refcount[b])

    def attach_prefix(self, slot: int, blocks: Sequence[int]) -> None:
        """Adopt already-filled `blocks` as the slot's leading logical
        blocks (prefix-cache hit): each gains a reference; content and
        any other holders are untouched. The slot must be freshly
        allocated (no blocks yet) — admission attaches before the first
        prefill chunk."""
        if not self._in_use[slot]:
            raise ValueError(f"slot {slot} is not allocated")
        if self._slot_blocks[slot]:
            raise ValueError(
                f"slot {slot} already holds blocks; prefix attach must "
                f"precede the first write"
            )
        for j, b in enumerate(blocks):
            self._ref_block(b)
            self._slot_blocks[slot].append(b)
            self.block_tables[slot, j] = b

    def needs_cow(self, slot: int, pos: int) -> bool:
        """Would a write at position `pos` hit a block the slot may not
        mutate in place (shared, or pinned by a prefix index)?"""
        lb = pos // self.block_size
        if lb >= len(self._slot_blocks[slot]):
            return False
        b = self._slot_blocks[slot][lb]
        return self._refcount[b] > 1 or b in self._indexed

    def cow_block(self, slot: int, pos: int) -> bool:
        """Copy-on-write: make the block holding position `pos` PRIVATE
        to `slot` before a write lands in it. No-op when the block is
        already exclusive (or unallocated — growth is `ensure_blocks`'
        job). Divergence inside a shared block copies ONLY that block:
        pool K/V and the quantized scale planes move in one jitted
        donated program, the slot's table repoints, and the original
        keeps its other holders / index entry. When the pool is dry and
        the only protection is an index entry (refcount 1), the entry
        is sacrificed instead of copying — the slot then owns the block
        outright. Returns False when a copy is required but no block is
        reclaimable (the engine's preemption signal)."""
        lb = pos // self.block_size
        if lb >= len(self._slot_blocks[slot]):
            return True
        b = self._slot_blocks[slot][lb]
        shared = self._refcount[b] > 1
        if not shared and b not in self._indexed:
            return True
        if not shared and self.free_blocks == 0:
            # index-only protection + dry pool: drop the entry (and its
            # subtree) rather than fail — cheaper than a preemption
            if self.evict_hook is not None:
                self.evict_hook(b)
            self._indexed.discard(b)
            return True
        if self.free_blocks == 0:
            return False
        new = self._take_block()
        self._refcount[new] = 1
        self.tree = _copy_block_fn()(
            self.tree, np.int32(b), np.int32(new)
        )
        self._slot_blocks[slot][lb] = new
        self.block_tables[slot, lb] = new
        self._decref(b)
        self.cow_copies += 1
        return True

    # -- introspection -----------------------------------------------------
    @property
    def active_slots(self) -> List[int]:
        return [s for s in range(self.slots) if self._in_use[s]]

    @property
    def occupancy(self) -> float:
        return float(self._in_use.sum()) / self.slots

    @property
    def free_blocks(self) -> int:
        """Reclaimable physical blocks: the plain free list PLUS cached
        prefix blocks (refcount 0, still indexed — evictable on
        demand). Backpressure and capacity math treat both as free."""
        return len(self._free_blocks) + len(self._cached_blocks)

    @property
    def live_blocks(self) -> int:
        """Physical blocks some slot references — each SHARED block
        counts ONCE (the whole point of prefix sharing: pool bytes
        track unique content, not per-request logical footprint)."""
        return self.num_blocks - self.free_blocks

    @property
    def window_live_blocks(self) -> int:
        """Window-layer blocks some slot holds (0 with no window layer)."""
        return self.window_num_blocks - len(self._window_free)

    @property
    def state_live_blocks(self) -> int:
        """State blocks some slot holds (0 with no layer that keeps one)."""
        return self.state_num_blocks - len(self._state_free)

    def state_block(self, slot: int) -> int:
        """The slot's state block (== `state_invalid_block`: none)."""
        return int(self.state_table[slot, 0])

    def window_slot_blocks(self, slot: int) -> Dict[int, int]:
        """logical block -> physical window block of a slot."""
        return dict(self._window_slot_blocks[slot])

    @property
    def cached_free_blocks(self) -> int:
        """Refcount-0 blocks kept alive only for the prefix index."""
        return len(self._cached_blocks)

    @property
    def shared_blocks(self) -> int:
        """Physical blocks referenced by more than one slot."""
        return int((self._refcount > 1).sum())

    @property
    def total_block_refs(self) -> int:
        """Sum of slot references — what the pool would hold with NO
        sharing; `total_block_refs - live-referenced blocks` is the
        dedup saving in blocks."""
        return int(self._refcount.sum())

    @property
    def bytes_deduplicated(self) -> int:
        """Pool bytes sharing saves right now vs a copy-per-reference
        layout: (refcount - 1) summed over shared blocks, in bytes."""
        extra = int(np.maximum(self._refcount - 1, 0).sum())
        return extra * self.bytes_per_block

    @property
    def pool_utilization(self) -> float:
        return self.live_blocks / self.num_blocks

    def _kind_bytes(self, kind) -> int:
        """HBM bytes one block pins across the layers of `kind` (one of
        `self.records`), AS HELD: the pool's padding (`ops.pool_kv_shape`,
        `ops.pool_latent_width`) and a quantized pool's scale planes
        included."""
        return self.layers[kind.name] * _block_bytes(self.avals[kind.name].values())

    def _family_bytes(self, family: str) -> int:
        return sum(
            self._kind_bytes(kind) for kind in self.records if kind.table == family
        )

    @functools.cached_property
    def bytes_per_block(self) -> int:
        """HBM bytes one block pins across every layer that keeps every
        token (a full layer's K + V, a latent layer's rows, PLUS the
        per-token scale planes when quantized — the true pool cost, so
        fixed-pool-bytes comparisons account the scale overhead)."""
        return self._family_bytes("blocks")

    @functools.cached_property
    def window_bytes_per_block(self) -> int:
        """HBM bytes one window block pins across the layers that keep a
        window."""
        return self._family_bytes("window")

    @functools.cached_property
    def state_bytes_per_block(self) -> int:
        """HBM bytes one state block pins across the layers that keep
        one, each layer's by the leaves its mixer names."""
        return self._family_bytes("state")

    @functools.cached_property
    def scale_bytes_per_block(self) -> int:
        """Of `bytes_per_block`, the bytes in planes the pool adds to its
        layers' own leaves: a quantized pool's scales, one f32 per (token
        slot, kv-head) for K and V (0 unquantized)."""
        from ..models.transformer import cache_leaves

        cfg = self.model.cfg
        return sum(
            self.layers[kind.name] * _block_bytes(
                aval for leaf, aval in self.avals[kind.name].items()
                if leaf not in cache_leaves(cfg, kind.name)[2]
            )
            for kind in self.records if kind.table == "blocks"
        )

    def pool_gauges(self) -> Dict[str, tuple]:
        """The pool as `/serve` counts it (`ServeMetrics.record_pool`): gauge
        (`serve/kinds.py::Kind.gauge`, for the kinds the model has) -> (live
        blocks of the family its layers ride, the bytes one such block pins
        across them, that family's blocks recycled while their request
        ran). The gauges' bytes add up to `bytes_live`."""
        live = {
            "blocks": self.live_blocks, "window": self.window_live_blocks,
            "state": self.state_live_blocks,
        }
        return {
            gauge: (
                live[family], nbytes,
                self.window_blocks_recycled if family == "window" else 0,
            )
            for gauge, (family, nbytes) in self._gauges.items()
        }

    @property
    def wire_dtype(self) -> str:
        """The pool's storage dtype name — the cache analog of the
        gradient hooks' wire format."""
        if self.quantized:
            return "int8"
        return str(np.dtype(self.model.cfg.dtype).name)

    @property
    def effective_slots(self) -> int:
        """How many WORST-CASE (max_seq_len) requests the pool can hold
        concurrently — the servable-slots-per-chip capacity figure the
        int8 pool roughly doubles at fixed pool bytes."""
        return self.num_blocks // self.blocks_per_seq

    @property
    def bytes_live(self) -> int:
        return (
            self.live_blocks * self.bytes_per_block
            + self.window_live_blocks * self.window_bytes_per_block
            + self.state_live_blocks * self.state_bytes_per_block
        )

    @functools.cached_property
    def dense_bytes_per_request(self) -> int:
        """What ONE slot costs in the dense (slots, max_seq_len, ...)
        layout — the paged-vs-dense comparison baseline (the share of a
        layer that keeps a state is its state block in either layout)."""
        from ..models.transformer import cache_leaves, layers_of

        cfg, total = self.model.cfg, 0
        for kind in layers_of(cfg):
            _, span, leaves = cache_leaves(cfg, kind)
            entry = sum(
                int(np.prod(shape)) * np.dtype(dtype).itemsize
                for shape, dtype in leaves.values()
            )
            total += entry * (1 if span == "row" else cfg.max_seq_len)
        return total

    def slot_blocks(self, slot: int) -> List[int]:
        return list(self._slot_blocks[slot])

    def exclusive_blocks(self, slot: int) -> int:
        """Blocks only `slot` references — what evicting it alone is
        guaranteed to reclaim (shared prefix blocks survive their
        holders, so eviction feasibility math must not count them)."""
        return sum(
            1 for b in self._slot_blocks[slot] if self._refcount[b] == 1
        )

    # -- migration payloads (serve/disagg/) --------------------------------
    def export_blocks(self, block_ids: Sequence[int]):
        """Host-side snapshot of the given physical blocks across every
        pool leaf, in table order — the KV MIGRATION payload. The
        gather is RAW: int8 payloads and their f32 scale planes come
        out bit-for-bit (no dequant round-trip), which is what makes a
        migrated quantized request token-exact on the landing pool.
        Returns a tree shaped like the pool with the block axis cut to
        `len(block_ids)`; scalar leaves pass through untouched."""
        idx = np.asarray(list(block_ids), np.int64)
        import jax

        return jax.tree_util.tree_map(
            lambda buf: (
                buf
                if getattr(buf, "ndim", 0) == 0
                else np.asarray(buf[idx])
            ),
            self.tree,
        )

    def import_blocks(self, dst_ids: Sequence[int], payload) -> None:
        """Land an `export_blocks` payload onto this pool's physical
        blocks `dst_ids` (same order, same count) — the migration
        receive. One jitted donated scatter per payload shape
        (`_import_blocks_fn`), the same in-place-update discipline as
        copy-on-write; under a TP mesh the replicated payload scatters
        into the KV-head-sharded pool shard-locally via GSPMD. Bytes
        land verbatim — dtype mismatches are a caller bug and raise."""
        import jax
        import jax.numpy as jnp

        self.tree = _import_blocks_fn()(
            self.tree,
            jax.tree_util.tree_map(jnp.asarray, payload),
            jnp.asarray(np.asarray(list(dst_ids), np.int32)),
        )

    def __repr__(self) -> str:
        return (
            f"PagedKVCache(slots={self.slots}, "
            f"blocks={self.live_blocks}/{self.num_blocks}, "
            f"block_size={self.block_size}, "
            f"active={int(self._in_use.sum())}, "
            f"shared={self.shared_blocks}, "
            f"cached={self.cached_free_blocks}, "
            f"wire={self.wire_dtype})"
        )
