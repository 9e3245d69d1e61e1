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

TWO KINDS of K/V state (a model whose `cfg.window_layers` marks some
layers as keeping a window): the full layers share the pool and the
tables described above; the WINDOW layers share a second, small pool
(`window_num_blocks` blocks, sized by the manager from the slots, the
window, the prefill chunk and the block size: a row never holds more
than `window_blocks_per_slot`) under a second table of the same
logical shape. A window-layer block wholly behind `position - window`
goes back to the window free list WHILE the request runs
(`ensure_blocks(..., first_pos=)`), its table entry turns invalid, and
the attention paths never read before a row's first attended block.
`tables()` hands the programs the pair; `free`, `bytes_live` and the
live-block counts account both kinds. A model with no window layer
gets exactly the single-kind tree, tables and accounting.

A THIRD KIND, the state block (a model some of whose layers' mixers keep
a fixed state and NO keys and values: `models/transformer.py::STATE_KINDS`,
today `LinearAttention` ("linear") and `GatedConv` ("conv")): such a layer
gets no K/V pool; it gets a pool of `slots` STATE BLOCKS, each the layer's
whole memory of one request, whose LEAVES ITS OWN MIXER NAMES
(`state_block_shapes`: a linear layer's `state` (H, dk, dv) float32 and
`conv`, the few pre-conv inputs behind the last token; a conv layer's
`tail` alone, the `conv_taps - 1` gated inputs behind it). The manager
allocates, frees and counts a block without naming a leaf: a layer whose
state is a tail and a layer whose state is a delta-rule matrix are one
kind of block in one table. A request
holds exactly one state block from `allocate()` to `free()`, whatever its
length, the same block index in every such layer, addressed through a
(slots, 1) table of its own that rides with the K/V tables (`tables()`:
one table a kind the model has, in `cfg.cache_kinds`' order; the "linear"
and the "conv" kind are handed the same table, as the "latent" kind is
handed the "full" kind's). An invalid entry drops the write, as for K/V,
and a block is never cleared: the mixer reads zero for a row whose first
token stands at position 0, so a block taken over from a retired or
preempted request starts clean. What is not carried for it: snapshots (a
preempted request prefills again from 0; a prefix cannot be shared).

A FOURTH KIND, the latent (a model whose `cfg.latent_layers` are
multi-head latent attention mixers, `models/transformer.py::
LatentAttention`): such a layer keeps ONE row of `cfg.latent_width` values a
token (a compressed latent and one shared rotary key) where a full layer
keeps K and V heads, so it gets ONE pool, (num_blocks, block_size,
latent_width), and no V pool. It keeps every token, as a full layer does:
latent blocks are allocated, refcounted and freed through the SAME tables
and free lists as the full kind's (a block id names the same tokens in
every layer that keeps every token), and `tables()` hands the programs
that table under the kind's name. A row of more than 128 values is held
in whole 128-value lane tiles (`ops.pool_latent_width`: 576 as 640, the
bytes the device would pad it to anyway), and `latent_bytes_per_block`
counts the rows as held. A shared prefix's latent blocks are attached and
copied on write with the rest of the tree (`cow_block` copies every leaf).
What is not carried for it: an int8 pool, tp, block migration
(`serve/engine.py` refuses them).

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
from collections import OrderedDict
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


def window_layers_of(cfg) -> tuple:
    """Per layer, whether it keeps a window of K/V only (a model
    configuration that says nothing keeps the whole context in all)."""
    return tuple(getattr(cfg, "window_layers", ())) or (False,) * cfg.n_layers


def state_layers_of(cfg) -> dict:
    """layer -> its kind, for the layers whose mixer keeps a state block
    and no K/V (`models.transformer.STATE_KINDS`; a model configuration
    that says nothing has none)."""
    from ..models.transformer import STATE_KINDS

    layers = getattr(cfg, "layers", None) or ()
    return {
        i: spec.attention for i, spec in enumerate(layers)
        if spec.attention in STATE_KINDS
    }


def latent_layers_of(cfg) -> tuple:
    """The layers that keep one latent row a token and no K and V heads
    (a model configuration that says nothing has none)."""
    return tuple(getattr(cfg, "latent_layers", ()))


def init_paged_cache(model, num_blocks: int, block_size: int,
                     quantized: bool = False,
                     window_blocks: Optional[int] = None,
                     state_blocks: Optional[int] = None):
    """Empty paged K/V pool tree for `model`: per layer one
    (num_blocks, block_size, kv_heads, head_dim) K and V (kv_heads in
    whole sublane tiles: `ops.paged_attention.pool_kv_heads`) — of
    `window_blocks` blocks instead in a layer the model's pattern marks
    as a window layer, and NONE in a layer whose mixer keeps a state
    (`state_layers_of`), which gets `state_blocks` state blocks of the
    leaves its mixer names (`models.transformer.state_block_shapes`) under
    the mixer's name; a latent layer gets ONE (num_blocks, block_size, latent_width)
    pool, `latent`, under its mixer's. Mirrors
    `models.generate.init_cache`'s structure minus the scalar "index"
    leaf (a shared pool has no per-row cursor).

    `quantized=True` switches the pool to INT8 K/V plus per-(block
    slot, kv-head) f32 scale planes `k_scale`/`v_scale` of shape
    (num_blocks, block_size, kv_heads) — one max-abs scale per stored
    token vector (`ops/quant.py::quantize_kv`), the granularity that
    lets quantize-on-scatter land a token in a shared block without
    requantizing the block's earlier tokens. The paged attention path
    detects the scale planes and dequantizes inside
    `ops.gather_paged_kv`, so the attention math stays cfg.dtype."""
    import jax.numpy as jnp

    from ..models.transformer import state_block_shapes
    from ..ops.paged_attention import pool_kv_shape, pool_latent_width

    cfg = model.cfg
    KV, Dh = pool_kv_shape(cfg.kv_heads, cfg.head_dim)
    windowed = window_layers_of(cfg)
    if any(windowed) and window_blocks is None:
        raise ValueError("a model with window layers needs window_blocks")
    stateful = state_layers_of(cfg)
    if stateful and state_blocks is None:
        raise ValueError(
            "a model with linear or conv layers needs state_blocks"
        )
    latent = latent_layers_of(cfg)
    if latent and quantized:
        raise ValueError("a latent pool has no int8 form")

    def one_layer(num_blocks):
        if quantized:
            return {
                "attn": {
                    "k": jnp.zeros(
                        (num_blocks, block_size, KV, Dh), jnp.int8
                    ),
                    "v": jnp.zeros(
                        (num_blocks, block_size, KV, Dh), jnp.int8
                    ),
                    "k_scale": jnp.zeros(
                        (num_blocks, block_size, KV), jnp.float32
                    ),
                    "v_scale": jnp.zeros(
                        (num_blocks, block_size, KV), jnp.float32
                    ),
                }
            }
        return {
            "attn": {
                "k": jnp.zeros((num_blocks, block_size, KV, Dh), cfg.dtype),
                "v": jnp.zeros((num_blocks, block_size, KV, Dh), cfg.dtype),
            }
        }

    def state_layer(kind):
        mixer, leaves = state_block_shapes(cfg, kind)
        return {mixer: {
            leaf: jnp.zeros((state_blocks,) + shape, dtype)
            for leaf, (shape, dtype) in leaves.items()
        }}

    def latent_layer():
        return {"latent_attn": {"latent": jnp.zeros(
            (num_blocks, block_size, pool_latent_width(cfg.latent_width)),
            cfg.dtype,
        )}}

    def layer(i):
        if i in stateful:
            return state_layer(stateful[i])
        if i in latent:
            return latent_layer()
        return one_layer(window_blocks if windowed[i] else num_blocks)

    return {f"layers_{i}": layer(i) for i in range(cfg.n_layers)}


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
        cfg = model.cfg
        M = cfg.max_seq_len
        windowed = window_layers_of(cfg)
        self.window_layers = sum(windowed)
        from ..models.transformer import state_block_shapes
        from ..ops.paged_attention import pool_kv_shape, pool_latent_width

        # heads and values of a token's K (or V) row as the pool holds them
        self.pool_kv_heads, self.pool_head_dim = pool_kv_shape(
            cfg.kv_heads, cfg.head_dim
        )
        # the layers whose mixer keeps a state block, and for each of them
        # leaf -> (shape, dtype) of its block: the leaves are the mixer's own
        stateful = state_layers_of(cfg)
        self.linear_layers = sum(kind == "linear" for kind in stateful.values())
        self.conv_layers = sum(kind == "conv" for kind in stateful.values())
        self.state_layers = len(stateful)
        self._state_leaves = [
            state_block_shapes(cfg, kind)[1] for kind in stateful.values()
        ]
        # the latent kind: one row a token in one pool a layer, under the
        # full kind's tables and free lists
        self.latent_layers = len(latent_layers_of(cfg))
        # values of a row as the model caches it, and as the pool holds
        # it: whole lane tiles past 128 values
        self.latent_values = cfg.latent_width if self.latent_layers else 0
        self.latent_width = pool_latent_width(self.latent_values)
        self.latent_rank = cfg.latent_kv_rank if self.latent_layers else 0
        self.full_layers = (
            cfg.n_layers - self.window_layers - self.state_layers
            - self.latent_layers
        )
        # the kinds of state the model's layers keep, in the order the
        # programs take their tables
        self.kinds = tuple(getattr(cfg, "cache_kinds", ("full",)))
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
        self.window = cfg.window if self.window_layers else None
        self.window_blocks_per_slot = self.window_num_blocks = 0
        if self.window_layers:
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
        self.tree = init_paged_cache(
            model, num_blocks, block_size, quantized=quantized,
            window_blocks=self.window_num_blocks or None,
            state_blocks=self.state_num_blocks or None,
        )
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
        if self.window_layers:
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
        kind of state the tuple of one table a kind (full layers' (n,
        nb), window layers' (n, nb), linear layers' (n, 1) state table,
        latent layers' (n, nb): the full kind's, conv layers' (n, 1): the
        state table again), in `cfg.cache_kinds`' order. `parked` slots' rows are handed
        over all-invalid. Always COPIES: the engine goes on growing and
        freeing rows while the program it handed a table to is still
        queued, and a program may read its host arguments late (the CPU
        client aliases them, a device client copies them behind the
        dispatch)."""
        have = {
            "full": (self.block_tables, self.invalid_block),
            "window": (self.window_tables, self.window_invalid_block),
            "linear": (self.state_table, self.state_invalid_block),
            "latent": (self.block_tables, self.invalid_block),
            "conv": (self.state_table, self.state_invalid_block),
        }
        out = [have[kind][0][rows].copy() for kind in self.kinds]
        for t, kind in zip(out, self.kinds):
            t[list(parked)] = have[kind][1]
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

    @property
    def pool_aval(self):
        """Shape and dtype of one layer's K (and V) pool, as
        `init_paged_cache` builds it — for callers that ask about the
        pool without naming the tree's keys."""
        import jax

        cfg = self.model.cfg
        return jax.ShapeDtypeStruct(
            (self.num_blocks, self.block_size, self.pool_kv_heads, self.pool_head_dim),
            np.int8 if self.quantized else cfg.dtype,
        )

    @property
    def state_aval(self):
        """Shape and dtype of one linear layer's `state` pool (None with
        no linear layer), for callers that ask about it without naming
        the tree's keys."""
        if not self.linear_layers:
            return None
        import jax

        from ..models.transformer import state_block_shapes

        shape, dtype = state_block_shapes(self.model.cfg, "linear")[1]["state"]
        return jax.ShapeDtypeStruct((self.state_num_blocks,) + shape, dtype)

    @property
    def latent_aval(self):
        """Shape and dtype of one latent layer's pool (None with no
        latent layer), as `pool_aval` is a full layer's."""
        if not self.latent_layers:
            return None
        import jax

        return jax.ShapeDtypeStruct(
            (self.num_blocks, self.block_size, self.latent_width),
            self.model.cfg.dtype,
        )

    @functools.cached_property
    def bytes_per_block(self) -> int:
        """HBM bytes one block pins across every layer that keeps every
        token (a full layer's K + V, a latent layer's rows, PLUS the
        per-token scale planes when quantized — the true pool cost, so
        fixed-pool-bytes comparisons account the scale overhead)."""
        cfg = self.model.cfg
        itemsize = (
            1 if self.quantized else np.dtype(cfg.dtype).itemsize
        )
        return (
            2 * self.full_layers * self.block_size * self.pool_kv_heads
            * self.pool_head_dim * itemsize
        ) + self.latent_bytes_per_block + self.scale_bytes_per_block

    @functools.cached_property
    def latent_bytes_per_block(self) -> int:
        """HBM bytes one block pins across the latent layers, AS HELD:
        rows of `latent_width` values (`ops.pool_latent_width`: 576
        cached values are held as 640)."""
        return (
            self.latent_layers * self.block_size * self.latent_width
            * np.dtype(self.model.cfg.dtype).itemsize
        )

    @property
    def latent_live_blocks(self) -> int:
        """Blocks some slot holds latent rows in (0 with no latent layer)."""
        return self.live_blocks if self.latent_layers else 0

    @functools.cached_property
    def window_bytes_per_block(self) -> int:
        """HBM bytes one window-kind block pins across the window layers."""
        cfg = self.model.cfg
        return (
            2 * self.window_layers * self.block_size * self.pool_kv_heads
            * self.pool_head_dim * np.dtype(cfg.dtype).itemsize
        )

    @functools.cached_property
    def state_bytes_per_block(self) -> int:
        """HBM bytes one state block pins across the layers that keep
        one, each layer's by the leaves its mixer names."""
        return sum(
            int(np.prod(shape)) * np.dtype(dtype).itemsize
            for leaves in self._state_leaves
            for shape, dtype in leaves.values()
        )

    @functools.cached_property
    def scale_bytes_per_block(self) -> int:
        """Scale-plane bytes one block pins (0 unquantized): one f32 per
        (token slot, kv-head) for K and V across every layer."""
        if not self.quantized:
            return 0
        cfg = self.model.cfg
        return 2 * cfg.n_layers * self.block_size * self.pool_kv_heads * 4

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
        cfg = self.model.cfg
        itemsize = np.dtype(cfg.dtype).itemsize
        kv_layers = cfg.n_layers - self.state_layers - self.latent_layers
        return (
            2 * kv_layers * cfg.max_seq_len * cfg.kv_heads * cfg.head_dim
            + self.latent_layers * cfg.max_seq_len * self.latent_values
        ) * itemsize + self.state_bytes_per_block

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
