"""Paged attention — two Pallas TPU kernels over the serve block pool.

The serve programs' attention (`models/transformer.py::Attention.
_decode_paged`) reads each row's K/V pages STRAIGHT out of the shared
block pool (`serve/cache.py`) and runs an online softmax over them, so a
row costs the pages it has — not the `nb * bs` keys its table could
address, which is what `gather_paged_kv` + the dense einsum move.
`paged_decode_attention` takes the decode step (one query token a row),
`paged_chunk_attention` a prefill chunk (L query tokens a row, causal
among themselves); the gather + einsum stays the path of int8 pools,
shapes Mosaic cannot tile and a window layer's chunk, and the reference
both kernels are tested against: `paged_kernel` is the one predicate.

Shape of the decode kernel (design per /opt/skills/guides/pallas_guide.md):

* ONE program, static shapes. Block tables, each row's page count and
  last attended position, and a flat WORK LIST ride in as scalar
  prefetch (SMEM); the list is as long as the live pages need, so the
  trip count — not a shape — follows the lengths, and nothing recompiles
  when they change.
* The list has TWO kinds of item. A (row, compute block) item is one
  row's queries against a block of its pages. A SHARED item is (group,
  compute block): a block that several rows' tables hold in common (a
  prefix the serve engine attached to each of them, `serve/prefix.py`)
  is copied from HBM ONCE a layer a step, and the group's rows meet that
  one copy, `SHARED_ROWS` of them at a time with their queries stacked:
  the KV heads one 32-bit row of a page holds (one of float32, two of
  bfloat16: a strided read, no value converted) against those heads'
  queries of all the stacked rows, one product a read (the key tiles go
  into the MXU once for all of them, and a query head meets no group's
  keys but its own and its word-mate's). What is shared is read from
  the tables on the device (`shared_runs`, in `_work_list`): two rows
  share a block when its pages are the same physical pages and it is
  FULL for both (no key of it past either row's last), in their LEADING
  run of such blocks. The shared items come first; each
  row's running max, sum and accumulator wait in VMEM ((B, H, .)
  float32, "nothing attended" when the call starts) for the first (row,
  block) item of its own, which goes on from them — the same online
  softmax over the same keys, in another order — and between two shared
  items, unless the second is the next block of the same rows: those
  stay stacked. Tables in which no two rows hold one page (an engine
  without `prefix_cache`) make the shared list EMPTY: a loop of zero
  trips, and the (row, block) items are every block, in row order, as
  they were before there was a shared list. The engine counts the same
  rule on the host (`shared_decode_keys`:
  `StepRecord.decode_shared_keys`).
* The pools stay in HBM (`memory_space=ANY`), viewed as
  (num_blocks, bs * KV, Dh): a page of all KV heads is one contiguous
  DMA. A compute block is `pages_per_block` pages copied, as many as the
  row has there, into one of two VMEM buffers; item i+1's pages (the
  first (row, block) item behind the last shared one, and the next
  row's first block, included) are in flight while item i computes.
* A row is bounded by its LEADING VALID table entries as well as its
  length: a parked lane (all-invalid table row, length M-1) has no work
  item, reads no page and returns zeros; an invalid entry past a live
  row's length is never read.
* A WINDOW (`window=`, a layer that attends the last `window` keys):
  the row's first page is the one that holds its first attended key
  (`page0`), keys before that key in the page are masked (`lo`), and the
  row costs min(length, window) keys. Entries before `page0` are never
  read and may be invalid (the serve cache frees them while the request
  lives). A window layer does not share: its scalars, body and compiled
  kernel are what they were before there was a shared list, and a layer
  without a window has nothing of the window's.
* In a (row, block) item all query heads meet all KV heads of a page in
  one MXU call: scores are (H, keys * KV) with column c = key * KV +
  kv_head, and the columns of another group's KV head are masked like
  keys past the length. That spends KV times the needed MXU work on a
  memory-bound step instead of strided sub-tile loads of single heads
  out of a packed page.
* The LATENT decode kernel (`latent_decode_attention`: a pool of one row
  of W values a token, which every head scores whole and whose leading
  `rank` values are its values) is the same program with the same two
  kinds of item, from the same `_work_list(..., share=True)`, and the
  easier case: a page has no KV heads to separate, so a (row, block) item
  is a row's H absorbed query heads against the block's T rows, and a
  SHARED item stacks `_latent_stacked_rows(H)` rows of its group with all
  their heads, (rows * H, W) against the ONE copy: score product, softmax
  update and value product over the same copy, in `LATENT_PASS_CHAINS`
  independent parts of the stack. The waiting state, `cont` and the empty
  shared list are as above.

The chunk kernel is the same design carried to L queries a row, where
the work is compute and not memory (`_chunk_kernel`):

* A grid step is one (row, block of `CHUNK_QUERY_BLOCK` queries); its
  trip count — not a shape — follows the pages that hold a position <=
  its last query, of the row's leading valid table entries: a later
  query block's keys are not visited by an earlier one, and one compiled
  program a chunk length serves every `start`.
* Each KV head meets only its own group's queries. A block's pages are
  copied once for all KV heads (double-buffered as above), then read
  back one head at a time by strided 32-bit loads into (KV, keys, Dh).
  Contracting every query head against every KV head, as the decode
  kernel does, would spend KV times the FLOPs where FLOPs are the cost.
* Scores are held transposed, (keys, rep * queries) a KV head: the
  softmax reduces down the sublanes and its running max and sum are
  lane-dense rows, which is what lets a 256-key block pay for itself.
* The causal mask is work: only a key block that crosses the diagonal
  (or the row's last page) applies it; blocks wholly below a query
  block's first position run unmasked.
* Queries ride in grouped by KV head, (row, query block, KV, rep *
  queries, Dh): two XLA transposes a call around the kernel.
* The LATENT chunk kernel (`latent_chunk_attention`) is that design over a
  pool that holds no KV heads, and it MAKES them: where a decode token
  moves a head's up-projections onto its query (absorbed: 2 r + dr values a
  pair), a chunk's L queries pay for a key's heads once (2 r (dn + dv)
  FLOPs a key and head) and then score dn + dr values and sum dv, less
  work from about 171 queries a key up (r 512, dn = dv 128, dr 64). A grid
  step is one (row, query block of `CHUNK_QUERY_BLOCK`, GROUP of heads,
  `_latent_chunk_heads`): what it holds in VMEM depends on neither L nor
  the head count. The block's queries ride in transposed ((heads, dn +
  rope lanes, queries): one XLA transpose a call, and one back for the
  outputs); the group's columns of the layer's `kv_b_proj` come as the
  layer stores them (no copy in the layer's program) and are laid out a
  head in VMEM once a step, `W_uk` (r, dn) and `W_uv` transposed (dv, r).
  The step walks the row's key blocks ONCE: a block's pages are copied as
  above (one buffer pair: a row of the pool is a token's latent beside its
  one rotary key), then, `LATENT_CHUNK_CHAINS` heads a pass, `k = c W_uk`
  (keys, dn) is written beside the block's rotary lanes, `v^T = W_uv^T c^T`
  (dv, keys) comes out of one product for the pass's heads, scores are
  (keys, queries) a head and the accumulator (dv, queries): every (key,
  head) is up-projected once a query block (once a chunk at the serve
  cells' 512 tokens), and the latents are read heads / group times. Only
  the blocks that hold the query block's own keys (and the row's last
  page) are masked.

Tolerance contract (tests/test_paged_attention.py tests to it). Scores
and the running max / sum are float32, probabilities are cast to the
value dtype before the value product, the accumulator is float32 and
the output is cast once — the dense path's recipe, with two
differences that both err on the side of precision: scores are NOT
rounded to the pool dtype before the softmax (the dense einsum's output
is), and normalisation happens after the value product. Against
`gather_paged_kv` + the dense einsum on the same operands each kernel
therefore agrees to float32 reassociation in float32 (max abs error
<= 2e-5 at unit-scale inputs) and to bfloat16 rounding of scores and
probabilities in bfloat16 (max abs error <= 2e-2 on outputs of unit
scale). No lower precision, no approximation, no truncated span.
The latent chunk kernel is held to the layer AS WRITTEN (`kv_up` and a
masked softmax over `gather_paged_latent`), with its rounding: operands of
the pool's dtype to the MXU, float32 accumulation, a key's up-projected
heads rounded to the pool's dtype as `kv_up` rounds them, scores and
softmax float32, probabilities cast before the value product; the same two
bounds.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import shard_map_fn
from .flash_attention import NEG_INF, _interpret_default, _partition

#: keys of one compute block (pages_per_block = KEYS_PER_BLOCK // bs). The
#: decode kernel: 256 read 566 GB/s of live K/V at the decode cell's
#: depths on a v5e, 512 the same (555), 128 less (475) — PERF.md, PR 25.
#: The chunk kernel: 256 / 512 / 1024 take 0.222 / 0.224 / 0.231 ms a
#: layer at 3072 keys and 0.051 / 0.053 / 0.087 at 512 — PERF.md, PR 28.
KEYS_PER_BLOCK = 256
#: queries of one grid step of the chunk kernel: the serve cells' largest
#: bucket whole (512 against 256: 0.222 against 0.255 ms, as above)
CHUNK_QUERY_BLOCK = 512
#: VMEM the chunk kernel may take of a v5e TensorCore's 128 MiB: a query
#: block of every head with its float32 accumulators stays resident
CHUNK_VMEM_BYTES = 96 * 1024 * 1024
#: heads of one grid step of the latent chunk kernel (`_latent_chunk_heads`):
#: a block of `CHUNK_QUERY_BLOCK` queries of that many heads stays resident
#: with its float32 accumulators, and the row's latents are read once a group
#: of heads and query block. On a v5e, 512 queries of 128 heads over 13312
#: keys, ms a call at 8 / 16 / 32 / 64 heads: 7.40 / 7.15 / 7.01 / 6.97 (at
#: 128 queries 32 / 64 / 128: 3.75 / 3.68 / 3.66) — PERF.md, PR 45
LATENT_CHUNK_HEADS = 32
#: heads one pass of that kernel's head loop takes, their chains independent
#: (1 / 2 / 4 at 32 heads a step: 7.76 / 7.26 / 7.01 ms)
LATENT_CHUNK_CHAINS = 4
#: rows of a group that meet a shared block of the decode kernel at a time:
#: one float32 sublane tile, so that ONE query head of all of them is one
#: strided vector of the stacked group
SHARED_ROWS = 8
#: stacked query rows (rows of a group x their heads) a shared block of the
#: LATENT decode kernel meets in one product (`_latent_stacked_rows`): a pass
#: of 256 takes 1.87 us on a v5e and one of 512 2.67, so four groups of 8
#: rows x 32 heads behind 768 pages each cost 0.446 against 0.600 ms a call,
#: and groups of 12, 9, 6, 5 cost 0.620 against 0.657 — PERF.md, PR 41.
LATENT_STACKED_QUERIES = 256
#: independent parts a stacked pass computes its rows in (loads, then the
#: parts' chains, then stores): 1 / 2 / 4 take 0.619 / 0.604 / 0.595 ms a call
#: at groups of 12, 9, 6, 5 and 0.446 / 0.436 / 0.430 at four of 8 — PERF.md,
#: PR 41
LATENT_PASS_CHAINS = 4
#: what the prefetched scalars (tables, work list) may take of the 1 MiB
#: of scalar memory of a TensorCore; the compiler keeps the rest.
SMEM_BYTES = 768 * 1024


def _head_shards(KV: int) -> int:
    """Devices the KV heads split over: the head axes of the open
    `partitioned_over` context when they divide KV, else 1 (a pool whose
    heads do not divide the axis is replicated —
    `parallel.tensor_parallel.kv_pool_spec` — and so is the kernel)."""
    if _partition.spec is None:
        return 1
    jmesh, _, head_axes = _partition.spec
    nh = math.prod(jmesh.shape[ax] for ax in head_axes)
    return nh if KV % nh == 0 else 1


def _pages_per_block(bs: int, nb: int) -> int:
    return max(1, min(KEYS_PER_BLOCK // bs, nb))


def _leading(valid):
    """Per row, how many leading entries of `valid` are true."""
    return jnp.sum(jnp.cumprod(valid, axis=1, dtype=jnp.int32), axis=1)


def _precision(dtype):
    """bfloat16 products are exact in one MXU pass; float32 pools take
    the multi-pass product. Named so that an ambient
    `jax_default_matmul_precision` (the test harness pins "highest")
    cannot ask Mosaic for a float32 contraction of bfloat16 operands."""
    return (
        lax.Precision.HIGHEST if dtype == jnp.float32
        else lax.Precision.DEFAULT
    )


def _page_copies(fn, tables_ref, first, have, pools, bufs, sems, slot):
    """Apply `fn` (start or wait) to the K and V copy of each page
    `tables_ref[first : first + have]` names, out of the pools in HBM
    into consecutive rows of the buffers' `slot`."""
    rows = pools[0].shape[1]  # bs * KV rows of Dh a page

    def one(i, carry):
        page = tables_ref[first + i]
        dst = pl.ds(pl.multiple_of(i * rows, rows), rows)
        for s, (pool, buf) in enumerate(zip(pools, bufs)):
            fn(pltpu.make_async_copy(
                pool.at[page], buf.at[slot, dst], sems.at[s, slot]
            ))
        return carry

    lax.fori_loop(0, have, one, 0)


def pool_kv_heads(kv_heads: int) -> int:
    """KV heads a paged pool holds for a model with `kv_heads` of them
    (`serve/cache.py` asks): as many, or — past one sublane tile of 8 and
    not in whole tiles — the next multiple of 8, the extra heads never
    written. The device pads a (KV, Dh) plane to whole tiles whatever it
    is told, and the kernels' view of a page as `bs * KV` rows is then a
    copy of the whole pool in every call (30 heads: eight 480 MB copies a
    decode step, compiled for v5e); in whole tiles it is the same bytes."""
    if kv_heads <= 8 or kv_heads % 8 == 0:
        return kv_heads
    return -(-kv_heads // 8) * 8


def pool_latent_width(width: int) -> int:
    """Values of a row a latent pool holds for a model that caches `width`
    of them a token (`serve/cache.py` asks): as many, or — past one lane
    tile of 128 and not in whole tiles — the next multiple of 128, the
    extra values zero. The device pads a row to whole lane tiles whatever
    it is told (576 values are held as 640), and Mosaic refuses to copy a
    page out of such a pool by the width it was told (compiled for v5e);
    in whole tiles the pool holds the same bytes and a page is one
    aligned copy."""
    if width <= 128 or width % 128 == 0:
        return width
    return -(-width // 128) * 128


def pool_head_pack(kv_heads: int, head_dim: int) -> int:
    """KV heads that ONE row of a paged pool holds side by side
    (`serve/cache.py` asks): 2 where a head is half the 128 lanes and the
    heads pair off into a count `pool_kv_heads` leaves alone, else 1. A
    pool of 64-wide heads is held as (num_blocks, bs, KV / 2, 128): heads
    2j and 2j + 1 of a token fill one lane row, the same bytes in the same
    order as (KV, 64) (the device would pad each 64-value row to 128
    lanes: twice the pool, and a copy at every view of a page), and both
    kernels take it as the 128-wide pool of KV / 2 heads it is:
    `pack_pool_heads` lays a query head against its own half of the row,
    `unpack_pool_heads` keeps that half of what comes back. Half of either
    product's terms are zeros, on the memory-bound side of a decode step;
    the bytes read are the model's own."""
    if 2 * head_dim != 128 or kv_heads % 2:
        return 1
    return 2 if pool_kv_heads(kv_heads // 2) == kv_heads // 2 else 1


def pool_kv_shape(kv_heads: int, head_dim: int) -> tuple:
    """(heads, values a head) of a token's K (or V) row as a paged pool
    holds it for a model of `kv_heads` heads of `head_dim` values:
    `pool_head_pack` heads side by side in a row, and as many rows as
    `pool_kv_heads` gives those. The same bytes a token either way."""
    pack = pool_head_pack(kv_heads, head_dim)
    return pool_kv_heads(kv_heads // pack), head_dim * pack


def to_pool_heads(q, k, v, held: int):
    """q (B, L, H, Dh), k and v (B, L, KV, Dh) as a pool of `held` >= KV
    heads takes them (`pool_kv_heads`): zero heads behind k's and v's
    and zero query groups behind q's, so that query head h still meets
    KV head h // (H // KV). With held == KV, the operands themselves."""
    B, L, KV, Dh = k.shape
    if held == KV:
        return q, k, v
    grow = lambda a: jnp.pad(
        a, [(0, held - KV) if i == 2 else (0, 0) for i in range(a.ndim)]
    )
    q = grow(q.reshape(B, L, KV, -1, Dh)).reshape(B, L, -1, Dh)
    return q, grow(k), grow(v)


def from_pool_heads(o, kv_heads: int, held: int):
    """The attention output (B, L, heads * Dh) of `to_pool_heads`'
    operands without the padded groups: (B, L, H * Dh)."""
    if held == kv_heads:
        return o
    B, L, _ = o.shape
    return o.reshape(B, L, held, -1)[:, :, :kv_heads].reshape(B, L, -1)


def pack_pool_heads(q, k, v, pack: int):
    """q (B, L, H, Dh), k and v (B, L, KV, Dh), rotated, as a pool that
    holds `pack` heads a row takes them (`pool_head_pack`): k's and v's
    heads side by side, (B, L, KV / pack, pack * Dh), and each query head
    widened to the row with its values against its own KV head's place in
    it and zeros against its row-mates': query head h still meets KV head
    h // (H // KV) and no other."""
    B, L, KV, Dh = k.shape
    H = q.shape[2]
    place = (jnp.arange(H) // (H // KV)) % pack  # of a query head's KV head
    mine = place[:, None] == jnp.arange(pack)[None, :]  # (H, pack)
    q = jnp.where(
        mine[:, :, None], q[:, :, :, None, :], jnp.zeros((), q.dtype)
    ).reshape(B, L, H, pack * Dh)
    k, v = (a.reshape(B, L, KV // pack, pack * Dh) for a in (k, v))
    return q, k, v


def unpack_pool_heads(o, kv_heads: int, pack: int, head_dim: int):
    """The attention output (B, L, H * pack * Dh) of `pack_pool_heads`'
    operands with each query head's own part of its row alone: (B, L,
    H * Dh). `kv_heads` and `head_dim` are the model's."""
    B, L, _ = o.shape
    # (row, place of the KV head, query head of its group, part, Dh): a
    # head of place p keeps part p of what it summed over the row
    o = o.reshape(B, L, kv_heads // pack, pack, -1, pack, head_dim)
    o = jnp.stack([o[:, :, :, p, :, p] for p in range(pack)], axis=3)
    return o.reshape(B, L, -1)


def _heads_split(dtype, kv: int) -> bool:
    """Whether strided reads of 32-bit rows can separate the `kv` heads of
    a page of this dtype (the chunk kernel's `split_heads`, the decode
    kernel's `_head_slab`): one head, float32, or bfloat16 heads in whole
    32-bit words."""
    if dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return kv == 1 or kv % (4 // jnp.dtype(dtype).itemsize) == 0


def decode_shares(pool, window=None) -> bool:
    """THE predicate of the shared pass: whether the decode kernel reads a
    block that several rows' tables hold ONCE a group (`shared_runs`), for
    this K pool as `paged_kernel` sees it (shape and dtype; under the
    `partitioned_over` context of the programs). Not a window layer's, and
    only where `_head_slab` can separate the heads of a page as one device
    holds them; everywhere else the kernel is what it was before there
    was a shared list (`_kernel(..., share=False)`: the only other
    variant). `paged_kernel` (the scalars to count), `paged_decode_
    attention` (which body to trace) and `serve.decode.step_shares_blocks`
    (whether the engine counts) all ask here. A LATENT pool (3-D: no KV
    heads to separate) shares wherever `_latent_kernel_for` gives its decode
    kernel: `_latent_decode_kernel` has the one form."""
    if len(pool.shape) == 3:
        return window is None
    kv = pool.shape[2] // _head_shards(pool.shape[2])
    return window is None and _heads_split(pool.dtype, kv)


def paged_kernel(L: int, pool, block_tables, window=None, rank=None):
    """Which kernel of this module takes the call: "decode"
    (`paged_decode_attention`), "chunk" (`paged_chunk_attention`) or
    None (the caller gathers the row's layout and runs the dense
    einsum) — THE predicate, read by `Attention._decode_paged` (which
    path to trace) and by `serve.decode.layer_paths` (which path the
    engine's counters name), from what both can see: the query length,
    the K pool and the block tables (arrays or `ShapeDtypeStruct`s;
    shapes and dtype alone are read), the layer's window, and the
    `partitioned_over` context a tp engine's programs apply the model
    under.

    Either kernel needs a floating pool of 2 or 4 bytes (the int8 pool
    dequantises in the gather); `Dh` AS THE POOL HOLDS IT a multiple of
    the 128 lanes (a model of 64-wide heads is held two heads a row,
    `pool_head_pack`, and both kernels take that pool as the 128-wide one
    of half as many heads it is: the caller lays its queries out with
    `pack_pool_heads`); a page
    whose `bs * KV` rows of `Dh` (KV as one device holds it) fill whole
    sublane tiles of the pool dtype (8 rows of float32, 16 of bfloat16),
    so page copies land tile-aligned in the VMEM buffer; and its
    prefetched scalars within scalar memory (tables and work list, with
    the two scalars a row that a window layer adds to the decode
    kernel's, or the shared list a layer without one does).

    "decode": one query token a row, with or without a window.
    "chunk": more than one, of a layer WITHOUT a window (a window
    layer's chunk gathers `window + L` keys: nothing to win), where the
    query length fills whole sublane tiles too (a 4-token bucket of the
    CPU tests is refused, not padded) and splits into query blocks of
    `CHUNK_QUERY_BLOCK`, on a float32 or bfloat16 pool whose KV heads
    (as one device holds them) fill 32-bit words — one head, or an even
    number of bfloat16 ones: the kernel separates the heads of a page
    by strided 32-bit reads.

    A LATENT pool ((num_blocks, bs, W): one row of W values a token, no
    KV heads; `models/transformer.py::LatentAttention`) answers
    "latent_decode" (`latent_decode_attention`), "latent_chunk"
    (`latent_chunk_attention`) or None, from the same shapes (W as the
    pool holds it: `pool_latent_width`) and `rank`,
    the leading values of a row that are its values: see
    `_latent_kernel_for`."""
    if len(pool.shape) == 3:
        return _latent_kernel_for(L, pool, block_tables, rank)
    _, bs, KV, Dh = pool.shape
    B, nb = block_tables.shape
    itemsize = jnp.dtype(pool.dtype).itemsize
    if not jnp.issubdtype(pool.dtype, jnp.floating) or itemsize not in (2, 4):
        return None
    tile = 32 // itemsize
    kv = KV // _head_shards(KV)
    if Dh % 128 or (bs * kv) % tile:
        return None
    if L == 1:
        P = _pages_per_block(bs, nb)
        items = B * -(-nb // P)
        # the shared list: a row's skipped blocks and first item, an item,
        # a flag and a next row a (row, whole block), the list's length
        shared = 2 * B + 3 * B * (nb // P) + 1 if decode_shares(pool, window) else 0
        fits = 4 * (B * nb + 2 * items + 4 * B + 1 + shared) <= SMEM_BYTES
        return "decode" if fits else None
    if (
        window is None
        and _heads_split(pool.dtype, kv)
        and L % tile == 0
        and L % min(L, CHUNK_QUERY_BLOCK) == 0
        and 4 * (B * nb + 2 * B) <= SMEM_BYTES
    ):
        return "chunk"
    return None


def _latent_kernel_for(L: int, pool, block_tables, rank):
    """`paged_kernel` for a latent pool (num_blocks, bs, W), of which the
    leading `rank` values of a row are also its values. Either kernel
    needs a float32 or bfloat16 pool outside any `partitioned_over`
    context (a latent pool is not partitioned: every head reads the whole
    row), pages of whole sublane tiles, rows and values that fill whole
    lane tiles (W and `rank` multiples of 128: a page is one aligned copy
    and the value product reads a lane-aligned slice of the page it
    scored), and its prefetched scalars within scalar memory (the decode
    kernel's with the shared list).
    "latent_decode":
    one query token a row. "latent_chunk": more, in whole sublane tiles
    and, past `CHUNK_QUERY_BLOCK`, whole query blocks of it: what a step of
    that kernel holds in VMEM is a query block of `LATENT_CHUNK_HEADS` heads
    at most, whatever L and the head count."""
    _, bs, W = pool.shape
    B, nb = block_tables.shape
    if pool.dtype not in (jnp.float32, jnp.bfloat16) or _partition.spec is not None:
        return None
    tile = 32 // jnp.dtype(pool.dtype).itemsize
    if bs % tile or W % 128 or not rank or rank % 128:
        return None
    if L == 1:
        P = _pages_per_block(bs, nb)
        items = B * -(-nb // P)
        # the shared list, as `paged_kernel` counts it for a K/V pool
        shared = 2 * B + 3 * B * (nb // P) + 1
        fits = 4 * (B * nb + 2 * items + 2 * B + 1 + shared) <= SMEM_BYTES
        return "latent_decode" if fits else None
    if (
        L % tile == 0
        and L % min(L, CHUNK_QUERY_BLOCK) == 0
        and 4 * (B * nb + 2 * B) <= SMEM_BYTES
    ):
        return "latent_chunk"
    return None


def shared_runs(block_tables, last, bs, P):
    """THE rule of what the decode kernel reads once for several rows:
    `run` (B, B), the leading compute blocks that rows r and r' read from
    ONE copy. Block j of the two is one block when its P table entries are
    equal and it is FULL for both (its last key is at or before each row's
    `last`: every entry valid, no key masked); `run[r, r']` counts such
    blocks from block 0 up to the first that is not one (a prefix cache
    attaches leading whole pages; a run may end inside a compute block,
    which is then each row's own), and is 0 on the diagonal. Row r's shared
    blocks are its longest run with any other row; rows r and r' are of one
    group at block j when j < run[r, r'] — an equivalence a block, each
    block's groups parts of the block before's. Tables in which no two rows
    hold one page (an engine without `prefix_cache`) give all 0.

    By pairs, because that is ONE reduction on the device; the host counts
    the same rule by sorting (`shared_decode_keys`)."""
    B, nb = block_tables.shape
    differ = block_tables[:, None, :] != block_tables[None, :, :]
    first = jnp.min(jnp.where(differ, jnp.arange(nb, dtype=jnp.int32), nb), axis=2)
    full = (last + 1) // (P * bs)  # a row's leading blocks with no key past `last`
    run = jnp.minimum(first // P, jnp.minimum(full[:, None], full[None, :]))
    return jnp.where(jnp.eye(B, dtype=bool), 0, run)


def shared_decode_keys(block_tables, lengths, num_blocks: int, bs: int) -> int:
    """Of the keys a decode step attends, how many its kernel reads from a
    copy that another row uses too: the host's count of what `_work_list`
    puts in the shared list for the same operands (`block_tables` (B, nb)
    with parked rows all-invalid, `lengths` (B,) the position each row
    writes), numpy arrays. `shared_runs`' rule by another road, because
    numpy pays by the call and not by the element: a row's longest run with
    any other row is its run with a NEIGHBOUR once the rows are sorted (the
    pages behind a row's full blocks replaced by a value no other row
    holds), so 2 (B - 1) comparisons of rows stand for the B * B pairs."""
    B, nb = block_tables.shape
    P = _pages_per_block(bs, nb)
    valid = block_tables < num_blocks
    lead = valid.argmin(axis=1)  # 0 where every entry is valid, too
    lead[valid[:, 0] & (lead == 0)] = nb
    n_pages = np.minimum(lead, np.clip(lengths // bs + 1, 0, nb))
    # pages of a row's full blocks: no key of them past its last
    own = (np.minimum(lengths, n_pages * bs - 1) + 1) // (P * bs) * P
    live = int(own.max(initial=0))
    if not live:
        return 0
    pages = np.where(
        np.arange(live)[None, :] < own[:, None], block_tables[:, :live],
        -1 - np.arange(B, dtype=block_tables.dtype)[:, None],
    )
    as_one = np.dtype((np.void, live * pages.dtype.itemsize))
    pages = pages[np.argsort(pages.view(as_one).ravel())]
    differ = pages[1:] != pages[:-1]
    run = differ.argmax(axis=1)  # pages a row and the next have in common
    run[~differ[:, 0] & (run == 0)] = live  # the same in every page
    run = np.maximum(np.append(run, 0), np.append(0, run))
    return int((run // P).sum()) * P * bs


def _shared_list(block_tables, last, bs, P):
    """The decode kernel's SHARED list for these tables, six scalar
    arrays: `skip` (B,), the blocks of a row's leading shared run, which
    leave its (row, block) items; the list, one item a group and block:
    the group's first row an item (B * nbs,), `off` (B,) — item i of row
    r is r's block i - off[r] — and the items' count (1,); `nxt`
    (B * nbs,), at [row, block] the group's next row after `row` (B at its
    last), by which the kernel walks a group; `cont` (B * nbs,), 1 at
    the [row, block] of an item whose rows are the rows of the item before
    it, one block on, and fit one stacked pass: the kernel leaves them
    stacked between the two.
    What is shared is `shared_runs`' to say; everything here is read off
    its (B, B) answer. A row is the FIRST of its group from the block at
    which its runs with every earlier row have ended to the end of its own
    run, so its items are consecutive blocks, in (row, block) order.
    Plain operations, few (a gather is three fusions: the kernel
    subtracts `off` itself), and no `lax.cond` around them: the compiler
    merges the layers' copies of these into one a step, and does not
    merge conditionals (16 layers: 38 fusions against 536, compiled for
    v5e)."""
    B, nb = block_tables.shape
    nbs = nb // P
    rows = jnp.arange(B, dtype=jnp.int32)
    blks = jnp.arange(nbs, dtype=jnp.int32)
    earlier = np.tri(B, k=-1, dtype=bool)  # [r, r']: r' < r
    run = shared_runs(block_tables, last, bs, P)
    skip = jnp.max(run, axis=1)
    # the blocks a row reads behind an earlier row of its group
    behind = jnp.max(jnp.where(earlier, run, 0), axis=1)
    mate = run[:, :, None] > blks  # (B, B, nbs): r' reads r's block j with it
    nxt = jnp.min(
        jnp.where(mate & earlier.T[:, :, None], rows[None, :, None], B), axis=1
    )
    # the item before (row, j) is (row, j - 1) with the same rows when the
    # row led block j - 1 too and no mate's run ends at j (the diagonal's
    # 0 "ends" at block 0, which continues nothing)
    leaves = jnp.sum(run[:, :, None] == blks, axis=1)
    cont = (
        (blks[None, :] > behind[:, None]) & (leaves == 0)
        & (jnp.sum(mate, axis=1) < SHARED_ROWS)
    )
    n = skip - behind  # items a row leads
    ends = jnp.cumsum(n)
    ids = jnp.arange(B * nbs, dtype=jnp.int32)
    row = jnp.sum(ids[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    row = jnp.minimum(row, B - 1)  # ids past the list: never read
    return (
        skip, row, ends - n - behind, ends[-1:],
        nxt.astype(jnp.int32).reshape(B * nbs),
        cont.astype(jnp.int32).reshape(B * nbs),
    )


def _work_list(block_tables, lengths, nblk, bs, P, window=None, share=False):
    """Scalar side of the kernel, in plain XLA (tiny, identical in every
    layer of a kind in a step, so the compiler keeps one copy): per row
    the pages to read — bounded by the length AND by the leading valid
    entries — and the last position attended; then the flat (row, block)
    list. With a `window`, also each row's first
    page `page0` (pages are counted from it, entries before it count as
    valid whatever they hold) and `lo`, the first attended key's offset in
    that page. With `share`, a row's items start behind its shared run,
    and `_shared_list`'s six arrays follow."""
    B, nb = block_tables.shape
    block_tables = block_tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    valid = block_tables < nblk
    if window is not None:
        first = jnp.clip(lengths - (window - 1), 0, nb * bs - 1)
        page0 = first // bs
        valid |= jnp.arange(nb)[None, :] < page0[:, None]
    n_pages = jnp.minimum(_leading(valid), jnp.clip(lengths // bs + 1, 0, nb))
    last = jnp.minimum(lengths, n_pages * bs - 1)  # -1 on a parked row
    if window is not None:
        n_pages = jnp.maximum(n_pages - page0, 0)  # from page0 on
        last = last - page0 * bs  # relative to page0's first key
        extra = (page0, first - page0 * bs)
    n_blocks = (n_pages + P - 1) // P
    if share:
        extra = _shared_list(block_tables, last, bs, P)
        n_blocks = n_blocks - extra[0]
    ends = jnp.cumsum(n_blocks)
    ids = jnp.arange(B * -(-nb // P), dtype=jnp.int32)
    row = jnp.sum(ids[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    row = jnp.minimum(row, B - 1)  # ids past the list: never read
    first = ends - n_blocks  # a row's first item
    if share:
        first = first - extra[0]  # is the block behind its shared run
    blk = ids - first[row]
    scalars = (block_tables.reshape(B * nb), n_pages, last, row, blk, ends[-1:])
    return scalars + extra if share or window is not None else scalars


def _online_softmax(s, v, m_prev, l_prev, acc_prev, precision):
    """One block's step of the online softmax on VALUES, for a shared
    item's slabs (a (row, block) item does the same on its scratch): the
    module's recipe, float32 scores `s` (rows, keys) against the running
    max, sum and accumulator, probabilities cast to the value dtype before
    the value product, a float32 accumulator. Returns the three, advanced."""
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)  # masked: exp(-1e30 - m) == 0 exactly
    alpha = jnp.exp(m_prev - m_new)
    return (
        m_new,
        alpha * l_prev + jnp.sum(p, axis=1, keepdims=True),
        alpha * acc_prev + jnp.dot(
            p.astype(v.dtype), v, precision=precision,
            preferred_element_type=jnp.float32,
        ),
    )


def _head_slab(buf, slot, w, KV, T):
    """Row key * KV + g of a page buffer is KV head g's key. One strided
    read of 32-bit rows takes the `pack` heads that share a word — one of
    float32, two of bfloat16: heads w * pack ... — of every key of
    `buf[slot]`: (pack * T, Dh) whose row t * pack + h is key t of head
    w * pack + h. No value is converted: a bfloat16 slab is the words
    read, seen as the two rows each of them is."""
    if KV == 1:
        return buf[slot]
    if buf.dtype == jnp.float32:
        return buf[slot, pl.ds(w, T, stride=KV), :]
    words = buf.bitcast(jnp.uint32)[slot, pl.ds(w, T, stride=KV // 2), :]
    return pltpu.bitcast(words, buf.dtype)


def _kernel(*refs, scale, nb, P, KV, windowed, share):
    # scalar prefetch (six; a window's two or the shared list's six
    # behind them), inputs, output, scratch (the shared pass's behind the
    # rest)
    (tables_ref, n_pages_ref, last_ref, item_row_ref, item_blk_ref,
     n_items_ref) = refs[:6]
    refs = refs[6:]
    if windowed:
        (page0_ref, lo_ref), refs = refs[:2], refs[2:]
    if share:
        (skip_ref, sh_row_ref, sh_off_ref, n_shared_ref, nxt_ref,
         cont_ref) = refs[:6]
        (q_g, m_g, l_g, acc_g, m_p, l_p, acc_p, member, count) = refs[17:]
        refs = refs[6:17]
    (q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, colpos, m_s, l_s,
     acc_s) = refs
    B, H = q_ref.shape[:2]
    rows = k_hbm.shape[1]  # bs * KV rows of Dh a page
    R = P * rows
    T = R // KV  # keys a compute block
    rep = H // KV
    n_items = n_items_ref[0]
    n_shared = n_shared_ref[0] if share else 0
    precision = _precision(kbuf.dtype)
    start, wait = (lambda cp: cp.start()), (lambda cp: cp.wait())

    # Column c of a score tile is (key c // KV, kv head c % KV). `colpos`
    # holds c where that head is the query head's group and a sentinel
    # past every limit elsewhere, so ONE compare masks both the foreign
    # heads and the keys past the row's last position.
    c = lax.broadcasted_iota(jnp.int32, (H, R), 1)
    j = lax.broadcasted_iota(jnp.int32, (H, R), 0)
    colpos[...] = jnp.where(c % KV == j // rep, c, jnp.int32(2**30))
    # pages a block does not have keep what the buffer held: zero V once
    # so 0 * stale is never 0 * NaN; rows with no work item return zeros
    vbuf[...] = jnp.zeros_like(vbuf)
    o_ref[...] = jnp.zeros_like(o_ref)
    if share:  # a row that has attended nothing yet
        m_p[...] = jnp.full(m_p.shape, NEG_INF, jnp.float32)
        l_p[...] = jnp.zeros(l_p.shape, jnp.float32)
        acc_p[...] = jnp.zeros(acc_p.shape, jnp.float32)

    def page_copies(item, slot, fn):
        """Apply `fn` (start or wait) to the K and V copy of every page
        compute block `item` of the (row, block) list has."""
        row = item_row_ref[item]
        first = item_blk_ref[item] * P
        have = jnp.minimum(n_pages_ref[row] - first, P)
        if windowed:
            first = first + page0_ref[row]
        _page_copies(
            fn, tables_ref, row * nb + first, have, (k_hbm, v_hbm),
            (kbuf, vbuf), sems, slot,
        )

    def shared_item(item):
        """(the group's first row, block) of item `item` of the shared
        list, and where `nxt` and `cont` have them."""
        row = sh_row_ref[item]
        blk = item - sh_off_ref[row]
        return row, blk, row * (nxt_ref.shape[0] // B) + blk

    def shared_copies(item, slot, fn):
        """The same for block `item` of the shared list: whole, out of
        its group's first row's table."""
        row, blk, _ = shared_item(item)
        first = row * nb + blk * P
        _page_copies(
            fn, tables_ref, first, P, (k_hbm, v_hbm), (kbuf, vbuf), sems, slot,
        )

    # the two lists are one queue of copies, the shared list first: item
    # i + 1's pages are in flight while item i computes
    if share:
        @pl.when(n_shared > 0)
        def _():
            shared_copies(0, 0, start)

    @pl.when((n_shared == 0) & (n_items > 0))
    def _():
        page_copies(0, 0, start)

    def shared_body(item, carry):
        """Item `item` of the shared list, a block of the group whose
        first row is `sh_row[item]`: ONE copy of its pages, and the rows of
        the group — walked by `nxt`, `SHARED_ROWS` at a time — meet it
        stacked: the
        keys of the KV heads one 32-bit row holds (`_head_slab`: one head,
        or two of bfloat16 with the other's columns masked) against those
        heads' queries of all of them in one MXU call, and no other's (a
        (row, block) item contracts every query head with every KV head,
        which is free only while it waits for its copy; a group's later
        rows wait for none). Every key of a shared block is attended by
        every row (the block is full): nothing is masked. A row's running
        max, sum and accumulator wait in `m_p`, `l_p`, `acc_p` (set to
        "nothing attended" when the call starts) for its first item of the
        (row, block) list, and between two shared items unless the second
        continues the first (`cont`): then the rows stay stacked."""
        slot = item % 2

        @pl.when(item + 1 < n_shared)
        def _():
            shared_copies(item + 1, 1 - slot, start)

        @pl.when((item + 1 == n_shared) & (n_items > 0))
        def _():
            page_copies(0, 1 - slot, start)

        first_row, blk, at = shared_item(item)
        shared_copies(item, slot, wait)
        nbs = nxt_ref.shape[0] // B
        # a row's H query heads sit in a slot of whole float32 tiles
        Hs = q_g.shape[0] // SHARED_ROWS
        slot_of = lambda i: pl.ds(pl.multiple_of(i * Hs, Hs), H)
        state = (m_g, l_g, acc_g)
        # KV heads a 32-bit row of a page holds: read together
        pack = 1 if KV == 1 else 4 // kbuf.dtype.itemsize

        # the rows are stacked already (the item before left them so) /
        # are to stay so for the item behind
        stay = cont_ref[at] == 1
        keep = cont_ref[shared_item(jnp.minimum(item + 1, n_shared - 1))[2]] == 1
        keep &= item + 1 < n_shared

        def some_rows(row):
            def stack(carry):
                row, n = carry
                member[n] = row
                at = slot_of(n)
                q_g[at, :] = q_ref[row].astype(jnp.float32)
                m_g[at, :] = m_p[row]
                l_g[at, :] = l_p[row]
                acc_g[at, :] = acc_p[row]
                return nxt_ref[row * nbs + blk], n + 1

            row, n = lax.while_loop(
                lambda c: (c[0] < B) & (c[1] < SHARED_ROWS), stack,
                (jnp.where(stay, B, row), jnp.where(stay, count[0], 0)),
            )
            count[0] = n
            # slots past n hold an earlier group's rows: computed, not kept
            slabs = range(KV // pack)
            # query head j of every slot is one strided vector; a slab's
            # heads' queries: head h's at rows h * rep * SHARED_ROWS
            heads = [[
                pl.ds(w * pack * rep + j, SHARED_ROWS, stride=Hs)
                for j in range(pack * rep)
            ] for w in slabs]
            take = lambda ref, w: jnp.concatenate(
                [ref[at, :] for at in heads[w]], axis=0
            )
            shape = (pack * rep * SHARED_ROWS, pack * T)
            if pack > 1:  # column c is head c % pack's key
                mine = lax.broadcasted_iota(jnp.int32, shape, 1) % pack == (
                    lax.broadcasted_iota(jnp.int32, shape, 0)
                    // (rep * SHARED_ROWS)
                )

            def scores(w, q):
                s = lax.dot_general(
                    q, _head_slab(kbuf, slot, w, KV, T),
                    (((1,), (1,)), ((), ())), precision=precision,
                    preferred_element_type=jnp.float32,
                ) * scale  # (pack * rep * SHARED_ROWS, pack * T)
                return jnp.where(mine, s, NEG_INF) if pack > 1 else s

            def put(w, new):
                for ref, value in zip(state, new):
                    for j, at in enumerate(heads[w]):
                        ref[at, :] = value[
                            j * SHARED_ROWS:(j + 1) * SHARED_ROWS
                        ]

            # every slab's loads, then their chains (product, row max,
            # `exp`, product), then every store: the chains are independent
            # and overlap only when no store to the state lies between
            # them (3.6 against 4.3 us an item: PERF.md, PR 36)
            qs = [take(q_g, w).astype(kbuf.dtype) for w in slabs]
            olds = [[take(ref, w) for ref in state] for w in slabs]
            ss = [scores(w, qs[w]) for w in slabs]
            news = [
                _online_softmax(
                    ss[w], _head_slab(vbuf, slot, w, KV, T), *olds[w],
                    precision,
                ) for w in slabs
            ]
            for w in slabs:
                put(w, news[w])

            def unstack(i, carry):
                r = member[i]
                at = slot_of(i)
                m_p[r] = m_g[at, :]
                l_p[r] = l_g[at, :]
                acc_p[r] = acc_g[at, :]

                # a row with no item of its own (every page shared and
                # whole) is finished by its last shared block
                @pl.when(
                    (blk + 1 == skip_ref[r])
                    & ((blk + 1) * P >= n_pages_ref[r])
                )
                def _():
                    o_ref[r] = (acc_g[at, :] / l_g[at, :]).astype(o_ref.dtype)

                return carry

            lax.fori_loop(0, jnp.where(keep, 0, n), unstack, 0)
            return row

        lax.while_loop(lambda row: row < B, some_rows, first_row)
        return carry

    def body(item, carry):
        slot = (n_shared + item) % 2

        @pl.when(item + 1 < n_items)
        def _():
            page_copies(item + 1, 1 - slot, start)

        row = item_row_ref[item]
        blk = item_blk_ref[item]

        if share:
            @pl.when(blk == skip_ref[row])
            def _():  # the row goes on from what its shared blocks left
                m_s[...] = m_p[row]
                l_s[...] = l_p[row]
                acc_s[...] = acc_p[row]
        else:
            @pl.when(blk == 0)
            def _():
                m_s[...] = jnp.full_like(m_s, NEG_INF)
                l_s[...] = jnp.zeros_like(l_s)
                acc_s[...] = jnp.zeros_like(acc_s)

        page_copies(item, slot, wait)
        q = q_ref[row]  # (H, Dh)
        k = kbuf[slot]  # (R, Dh)
        v = vbuf[slot]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        ) * scale  # (H, R)
        limit = (last_ref[row] - blk * T + 1) * KV
        keep = colpos[...] < limit
        if windowed:
            # the first block starts before the window: its leading keys
            # (a foreign head's sentinel passes here and fails `limit`)
            keep &= colpos[...] >= jnp.where(blk == 0, lo_ref[row], 0) * KV
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # masked: exp(-1e30 - m) == 0 exactly
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = alpha * acc_s[...] + jnp.dot(
            p.astype(v.dtype), v, precision=precision,
            preferred_element_type=jnp.float32,
        )
        m_s[...] = m_new

        @pl.when((blk + 1) * P >= n_pages_ref[row])
        def _():
            o_ref[row] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)

        return carry

    if share:
        lax.fori_loop(0, n_shared, shared_body, 0)
    lax.fori_loop(0, n_items, body, 0)


def _shared_vmem_limit(B, H, Dh, R, itemsize) -> int:
    """Scoped VMEM the kernel with a shared pass asks for: q and the output
    whole, two page buffers of R rows each for K and V, `colpos` and room
    for eight live (H, R) float32 tiles, and a (max, sum, accumulator) —
    the max and the sum a 128-lane row a head — for every row, for the
    `SHARED_ROWS` stacked ones (with their queries) and for the running
    one; never under the 16 MB a v5e kernel gets unasked, which hold it at
    the serve cells' 32 rows (9 MB) but not at 256 (22 MB), where the
    kernel without a shared pass still fits them."""
    state = -(-H // 8) * 8 * 2 * 512 + H * Dh * 4
    return max(16 << 20, (
        2 * B * H * Dh * itemsize + 4 * R * Dh * itemsize + 9 * H * R * 4
        + (B + SHARED_ROWS + 1) * state + SHARED_ROWS * H * Dh * 4
    ))


@functools.partial(
    jax.jit, static_argnames=("scale", "interpret", "window", "share")
)
def _per_device(
    q, pool_k, pool_v, block_tables, lengths, *, scale, interpret, window=None,
    share=False,
):
    """The kernel call on one device's operands. A `jax.jit` of its own
    so that the layers of a step share ONE trace and ONE lowering of the
    kernel: traced per layer, 16 layers cost 25 s of host time in every
    start of the serve benchmark, compile-cache hit or not (PERF.md,
    PR 25). The compiler inlines the calls and each keeps its layer's
    scope path (`.../cache_attention/jit(_per_device)/...`)."""
    B, H, Dh = q.shape
    nblk, bs, KV, _ = pool_k.shape
    nb = block_tables.shape[1]
    P = _pages_per_block(bs, nb)
    rows = bs * KV
    scalars = _work_list(block_tables, lengths, nblk, bs, P, window, share)
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = lambda: pl.BlockSpec(memory_space=pl.ANY)
    f32 = jnp.float32
    scratch = [
        pltpu.VMEM((2, P * rows, Dh), pool_k.dtype),
        pltpu.VMEM((2, P * rows, Dh), pool_v.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((H, P * rows), jnp.int32),
        pltpu.VMEM((H, 1), f32),
        pltpu.VMEM((H, 1), f32),
        pltpu.VMEM((H, Dh), f32),
    ]
    if share:
        # the stacked rows' queries and state, a row in whole float32
        # tiles; every row's waiting state
        G = SHARED_ROWS * -(-H // 8) * 8
        scratch += [
            pltpu.VMEM((G, Dh), f32),
            pltpu.VMEM((G, 1), f32),
            pltpu.VMEM((G, 1), f32),
            pltpu.VMEM((G, Dh), f32),
            pltpu.VMEM((B, H, 1), f32),
            pltpu.VMEM((B, H, 1), f32),
            pltpu.VMEM((B, H, Dh), f32),
            pltpu.SMEM((SHARED_ROWS,), jnp.int32),
            pltpu.SMEM((1,), jnp.int32),
        ]
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, nb=nb, P=P, KV=KV,
            windowed=window is not None, share=share,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(1,),
            in_specs=[vmem(), hbm(), hbm()],
            out_specs=vmem(),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY,),
            vmem_limit_bytes=_shared_vmem_limit(
                B, H, Dh, P * rows, pool_k.dtype.itemsize
            ) if share else None,
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(
        *scalars, q,
        pool_k.reshape(nblk, rows, Dh), pool_v.reshape(nblk, rows, Dh),
    )


def _on_kv_shards(local, q, pool):
    """`local(q, pool_k, pool_v, tables, per-row scalars)` as it is, or,
    under `ops.partitioned_over`, per device through a `shard_map`: q
    (heads next to last) and the pools split on KV heads over the head
    axes, rows whole on every device (a serve program shards no batch
    axis), tables and scalars whole, no collective."""
    if _partition.spec is None:
        return local
    jmesh, _, head_axes = _partition.spec
    P = jax.sharding.PartitionSpec
    h = head_axes if _head_shards(pool.shape[2]) > 1 else None
    heads = P(*[None] * (q.ndim - 2), h, None)
    kv = P(None, None, h, None)
    return shard_map_fn(local, jmesh, (heads, kv, kv, P(), P()), heads)


def paged_decode_attention(
    q, pool_k, pool_v, block_tables, lengths, scale=None, *, interpret=None,
    window=None,
):
    """One decode token a row against the paged block pool.

    q: (B, H, Dh); pool_k / pool_v: (num_blocks, bs, KV, Dh), the serve
    engine's pool, layout unchanged; block_tables: (B, nb) int32 (entries
    == num_blocks mark unallocated logical blocks); lengths: (B,) int32.
    Row b attends the keys at absolute positions <= lengths[b] — this
    step's own token included, which `kv_scatter` wrote first — through
    its leading valid table entries; returns (B, H, Dh) in q's dtype.
    GQA: query head j reads KV head j // (H // KV), un-repeated.
    `window` (static int): row b attends only the last `window` of those
    keys, positions > lengths[b] - window; table entries wholly before
    them are not read and may be invalid.

    Under `ops.partitioned_over(mesh, batch_axes, head_axes)` the call
    runs per device through a `shard_map` — q split on heads and the
    pools on KV heads over `head_axes`, tables and lengths whole, no
    collective — because a Mosaic kernel is a custom call GSPMD cannot
    partition (the serve step opens the context for a tp engine).

    Callers check `paged_kernel` first; precision contract in the
    module docstring.
    """
    Dh = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    if interpret is None:
        interpret = _interpret_default()
    local = functools.partial(
        _per_device, scale=scale, interpret=interpret, window=window,
        share=decode_shares(pool_k, window),
    )
    return _on_kv_shards(local, q, pool_k)(
        q, pool_k, pool_v, block_tables, lengths
    )


def _chunk_kernel(
    tables_ref, n_pages_ref, start_ref, q_ref, k_hbm, v_hbm, o_ref,
    kbuf, vbuf, sems, kd, vd, m_s, l_s, acc_s, *, scale, nb, P, KV, bs, bq,
):
    """Grid step (b, i): queries `start[b] + i * bq ...` of row b, grouped
    by KV head as (KV, rep * bq, Dh) with row r * bq + t = (query head
    g * rep + r, query t), against the key blocks that hold a position
    <= the last of them."""
    b, i = pl.program_id(0), pl.program_id(1)
    N = q_ref.shape[1]  # rep * bq queries a KV head
    T = P * bs  # keys a compute block
    precision = _precision(kbuf.dtype)
    first_q = start_ref[b] + i * bq
    # pages that hold a position <= the last query's, of the row's
    # leading valid ones: what this step reads, and no entry behind them
    n_pages = jnp.minimum(n_pages_ref[b], (first_q + bq + bs - 1) // bs)
    n_blocks = (n_pages + P - 1) // P

    @pl.when((b == 0) & (i == 0))
    def _():
        # pages a block does not have keep what the buffer held: zero V
        # once so that 0 * stale is never 0 * NaN
        vbuf[...] = jnp.zeros_like(vbuf)

    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    def page_copies(blk, slot, fn):
        _page_copies(
            fn, tables_ref, b * nb + blk * P, jnp.minimum(n_pages - blk * P, P),
            (k_hbm, v_hbm), (kbuf, vbuf), sems, slot,
        )

    @pl.when(n_blocks > 0)
    def _():
        page_copies(0, 0, lambda cp: cp.start())

    def split_heads(buf, slot, dst):
        """Row key * KV + g of a buffer is KV head g's key: strided reads
        turn the packed pages into (KV, T, Dh), so that a head meets its
        own group's queries and no other's. Mosaic strides 32-bit rows
        only: a bfloat16 buffer is read as words that hold heads (2w,
        2w + 1) of a key, the even head in the low half, and a bfloat16
        is the high half of the float32 of its value."""
        if KV == 1:
            dst[0] = buf[slot]
        elif buf.dtype == jnp.float32:
            for g in range(KV):
                dst[g] = buf[slot, pl.ds(g, T, stride=KV), :]
        else:
            words = buf.bitcast(jnp.uint32)
            for w in range(KV // 2):
                x = words[slot, pl.ds(w, T, stride=KV // 2), :]
                for h, bits in enumerate((x << 16, x & jnp.uint32(0xFFFF0000))):
                    dst[2 * w + h] = lax.bitcast_convert_type(
                        bits, jnp.float32
                    ).astype(dst.dtype)

    def attend(key0, masked):
        """Every KV head's keys of one block against its query group.
        Scores are TRANSPOSED, (keys, N): the softmax's max and sum run
        down the sublanes (elementwise across vregs, one short reduction
        at the end) and its running state is (1, N) rows, where (N, keys)
        scores pay a cross-lane reduction and a column of N / 8 vregs an
        operation in every block: 26 us of a 34 us block (PERF.md, PR 28)."""
        if masked:
            key = lax.broadcasted_iota(jnp.int32, (T, N), 0)
            t = lax.rem(lax.broadcasted_iota(jnp.int32, (T, N), 1), bq)
            keep = key <= jnp.minimum(first_q + t, n_pages * bs - 1) - key0

        def head(g, carry):
            s = lax.dot_general(
                kd[g], q_ref[g], (((1,), (1,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32,
            ) * scale  # (T, N)
            if masked:
                s = jnp.where(keep, s, NEG_INF)
            m_prev = m_s[g]  # (1, N)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            # masked: exp(-1e30 - m) == 0 exactly, key 0 is in every
            # query's first block so m is a score from there on
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_s[g] = alpha * l_s[g] + jnp.sum(p, axis=0, keepdims=True)
            acc_s[g] = alpha * acc_s[g] + lax.dot_general(
                vd[g], p.astype(vd.dtype), (((0,), (0,)), ((), ())),
                precision=precision, preferred_element_type=jnp.float32,
            )  # (Dh, N)
            m_s[g] = m_new
            return carry

        lax.fori_loop(0, KV, head, 0)

    def body(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            page_copies(blk + 1, 1 - slot, lambda cp: cp.start())

        page_copies(blk, slot, lambda cp: cp.wait())
        split_heads(kbuf, slot, kd)
        split_heads(vbuf, slot, vd)
        key0 = blk * T
        # the mask is work: only a block that crosses the diagonal (a
        # key past the first query) or the row's last valid page pays it
        masked = (key0 + T - 1 > first_q) | (key0 + T > n_pages * bs)

        @pl.when(masked)
        def _():
            attend(key0, True)

        @pl.when(jnp.logical_not(masked))
        def _():
            attend(key0, False)

        return carry

    lax.fori_loop(0, n_blocks, body, 0)
    for g in range(KV):
        l = l_s[g]  # 0 on a row with no valid page: zeros, not 0 / 0
        o_ref[g] = jnp.where(l > 0, acc_s[g] / l, 0.0).T.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _chunk_per_device(
    q, pool_k, pool_v, block_tables, starts, *, scale, interpret
):
    """The chunk kernel on one device's operands; a `jax.jit` of its own
    for the reason `_per_device` is one."""
    B, L, H, Dh = q.shape
    nblk, bs, KV, _ = pool_k.shape
    nb = block_tables.shape[1]
    rep = H // KV
    bq = min(L, CHUNK_QUERY_BLOCK)
    # q and the output double-buffered, the accumulators: within VMEM
    while H * bq * Dh * (4 * q.dtype.itemsize + 4) > CHUNK_VMEM_BYTES // 2:
        bq //= 2
    nq = L // bq
    P = _pages_per_block(bs, nb)
    rows = bs * KV
    block_tables = block_tables.astype(jnp.int32)
    scalars = (
        block_tables.reshape(B * nb), _leading(block_tables < nblk),
        starts.astype(jnp.int32),
    )
    # query head g * rep + r of query i * bq + t -> [i, g, r * bq + t]
    grouped = (B, nq, KV, rep * bq, Dh)
    qg = q.reshape(B, nq, bq, KV, rep, Dh).transpose(0, 1, 3, 4, 2, 5)
    block = pl.BlockSpec(
        (None, None, KV, rep * bq, Dh), lambda b, i, *_: (b, i, 0, 0, 0)
    )
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    f32 = jnp.float32
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel, scale=scale, nb=nb, P=P, KV=KV, bs=bs, bq=bq
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B, nq),
            in_specs=[block, hbm, hbm],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((2, P * rows, Dh), pool_k.dtype),
                pltpu.VMEM((2, P * rows, Dh), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((KV, P * bs, Dh), pool_k.dtype),
                pltpu.VMEM((KV, P * bs, Dh), pool_v.dtype),
                pltpu.VMEM((KV, 1, rep * bq), f32),
                pltpu.VMEM((KV, 1, rep * bq), f32),
                pltpu.VMEM((KV, Dh, rep * bq), f32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(grouped, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY, pltpu.ARBITRARY),
            vmem_limit_bytes=CHUNK_VMEM_BYTES,
        ),
        interpret=interpret,
        name="paged_chunk_attention",
    )(
        *scalars, qg.reshape(grouped),
        pool_k.reshape(nblk, rows, Dh), pool_v.reshape(nblk, rows, Dh),
    )
    out = out.reshape(B, nq, KV, rep, bq, Dh).transpose(0, 1, 4, 2, 3, 5)
    return out.reshape(B, L, H, Dh)


def paged_chunk_attention(
    q, pool_k, pool_v, block_tables, starts, scale=None, *, interpret=None
):
    """A prefill chunk of L query tokens a row against the paged block
    pool: the decode kernel's design carried to L queries.

    q: (B, L, H, Dh); pools and block_tables as `paged_decode_attention`
    takes them; starts: (B,) int32. Query i of row b sits at absolute
    position `starts[b] + i` and attends the keys at positions <= it —
    the chunk's own, which `kv_scatter` wrote first, included — through
    the row's leading valid table entries; returns (B, L, H, Dh) in q's
    dtype. A padded query past the row's valid pages attends what the
    row has and stays finite; a row with no valid page returns zeros.

    Work follows the live keys: a (row, query block) grid step walks
    `ceil(min(position of its last query + 1, valid keys) /
    KEYS_PER_BLOCK)` key blocks, a trip count and not a shape, so
    one compiled program serves every `start`. Each block's pages are
    copied once for all KV heads, double-buffered, and read back one KV
    head at a time (a strided read), so a KV head is multiplied with its
    own `rep` query heads alone. Blocks wholly below a query block's
    first position run without a mask.

    Under `ops.partitioned_over` it runs per device on its KV-head shard
    as the decode kernel does. Callers check `paged_kernel` first;
    precision contract in the module docstring.
    """
    Dh = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    if interpret is None:
        interpret = _interpret_default()
    local = functools.partial(
        _chunk_per_device, scale=scale, interpret=interpret
    )
    return _on_kv_shards(local, q, pool_k)(
        q, pool_k, pool_v, block_tables, starts
    )


# --- a latent pool: one shared row a token, every head's key and value -------

def gather_paged_latent(pool, block_tables):
    """Each row's LOGICAL layout out of a latent pool: pool (num_blocks,
    bs, W), block_tables (B, nb) -> (B, nb * bs, W) in position order.
    Invalid entries clamp to a real block and the caller's mask hides
    them, as in `gather_paged_kv`: the path of the shapes no kernel takes
    and the reference both latent kernels are tested against."""
    nblk, bs, W = pool.shape
    B, nb = block_tables.shape
    return pool[block_tables].reshape(B, nb * bs, W)


def _latent_stacked_rows(H: int) -> int:
    """Rows of a group that meet a shared latent block at a time: whole
    sublane tiles of `SHARED_ROWS` (what `_shared_list`'s `cont` counts a
    stacked pass as holding), and enough of them that the rows' H absorbed
    query heads fill the MXU's passes over a key tile: `LATENT_STACKED_QUERIES`
    stacked query rows or more. From the head count alone: 8 rows at 32
    heads and at 128, 16 at 16."""
    return SHARED_ROWS * max(1, -(-LATENT_STACKED_QUERIES // (SHARED_ROWS * H)))


def _latent_decode_kernel(
    tables_ref, n_pages_ref, last_ref, item_row_ref, item_blk_ref,
    n_items_ref, skip_ref, sh_row_ref, sh_off_ref, n_shared_ref, nxt_ref,
    cont_ref, q_ref, pool_hbm, o_ref, buf, sems, m_s, l_s, acc_s,
    q_g, m_g, l_g, acc_g, m_p, l_p, acc_p, member, count,
    *, scale, nb, P, rank,
):
    """`_kernel(..., share=True)` for a latent pool: a page is `bs` rows of
    W values that every query head scores whole and whose leading `rank`
    values are its values too, so ONE copy a page serves both products, no
    column belongs to a foreign head, and a shared block meets the stacked
    rows' heads in one product: nothing to separate."""
    B, H = q_ref.shape[:2]
    bs = pool_hbm.shape[1]
    T = P * bs  # keys a compute block
    nbs = nxt_ref.shape[0] // B
    Hs = q_g.shape[0] // member.shape[0]  # a stacked row's slot: whole tiles
    n_items = n_items_ref[0]
    n_shared = n_shared_ref[0]
    precision = _precision(buf.dtype)
    start, wait = (lambda cp: cp.start()), (lambda cp: cp.wait())
    col = lax.broadcasted_iota(jnp.int32, (H, T), 1)
    # pages a block does not have keep what the buffer held, and the rows
    # are values too: zero once so that 0 * stale is never 0 * NaN; rows
    # with no work item return zeros
    buf[...] = jnp.zeros_like(buf)
    o_ref[...] = jnp.zeros_like(o_ref)
    # a row that has attended nothing yet
    m_p[...] = jnp.full(m_p.shape, NEG_INF, jnp.float32)
    l_p[...] = jnp.zeros(l_p.shape, jnp.float32)
    acc_p[...] = jnp.zeros(acc_p.shape, jnp.float32)

    def page_copies(item, slot, fn):
        row = item_row_ref[item]
        first = item_blk_ref[item] * P
        have = jnp.minimum(n_pages_ref[row] - first, P)
        _page_copies(
            fn, tables_ref, row * nb + first, have, (pool_hbm,), (buf,),
            sems, slot,
        )

    def shared_item(item):
        """(the group's first row, block) of item `item` of the shared
        list, and where `nxt` and `cont` have them."""
        row = sh_row_ref[item]
        blk = item - sh_off_ref[row]
        return row, blk, row * nbs + blk

    def shared_copies(item, slot, fn):
        """Block `item` of the shared list: whole, out of its group's first
        row's table."""
        row, blk, _ = shared_item(item)
        _page_copies(
            fn, tables_ref, row * nb + blk * P, P, (pool_hbm,), (buf,), sems,
            slot,
        )

    # the two lists are one queue of copies, the shared list first: item
    # i + 1's pages are in flight while item i computes
    @pl.when(n_shared > 0)
    def _():
        shared_copies(0, 0, start)

    @pl.when((n_shared == 0) & (n_items > 0))
    def _():
        page_copies(0, 0, start)

    def shared_body(item, carry):
        """Item `item` of the shared list, a block of the group whose first
        row is `sh_row[item]`: ONE copy of its pages, and the rows of the
        group — walked by `nxt`, as many at a time as `member` holds — meet
        it stacked, all H heads of each: (rows * H, W) queries against the
        (T, W) copy, one online-softmax update, one value product over the
        same copy. Every key of a shared block is attended by every row (the
        block is full): nothing is masked. A row's running max, sum and
        accumulator wait in `m_p`, `l_p`, `acc_p` for its first item of the
        (row, block) list, and between two shared items unless the second
        continues the first (`cont`): then the rows stay stacked."""
        slot = item % 2

        @pl.when(item + 1 < n_shared)
        def _():
            shared_copies(item + 1, 1 - slot, start)

        @pl.when((item + 1 == n_shared) & (n_items > 0))
        def _():
            page_copies(0, 1 - slot, start)

        first_row, blk, at = shared_item(item)
        shared_copies(item, slot, wait)
        slot_of = lambda i: pl.ds(pl.multiple_of(i * Hs, Hs), H)
        # the rows are stacked already (the item before left them so) /
        # are to stay so for the item behind
        stay = cont_ref[at] == 1
        keep = cont_ref[shared_item(jnp.minimum(item + 1, n_shared - 1))[2]] == 1
        keep &= item + 1 < n_shared

        def some_rows(row):
            def stack(carry):
                row, n = carry
                member[n] = row
                q_g[slot_of(n), :] = q_ref[row]
                m_g[slot_of(n), :] = m_p[row]
                l_g[slot_of(n), :] = l_p[row]
                acc_g[slot_of(n), :] = acc_p[row]
                return nxt_ref[row * nbs + blk], n + 1

            row, n = lax.while_loop(
                lambda c: (c[0] < B) & (c[1] < member.shape[0]), stack,
                (jnp.where(stay, B, row), jnp.where(stay, count[0], 0)),
            )
            count[0] = n
            # slots past n hold an earlier group's rows: computed, not kept
            k = buf[slot]  # (T, W)
            # the stack in `LATENT_PASS_CHAINS` independent parts: every
            # part's loads, then their chains (product, row max, `exp`,
            # product), then every store, so that one part's softmax runs
            # under another's products (`_kernel`'s shared pass: PERF.md,
            # PR 36)
            part = q_g.shape[0] // LATENT_PASS_CHAINS
            parts = [pl.ds(c * part, part) for c in range(LATENT_PASS_CHAINS)]
            state = (m_g, l_g, acc_g)
            qs = [q_g[rows, :] for rows in parts]
            olds = [[ref[rows, :] for ref in state] for rows in parts]
            ss = [
                lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())), precision=precision,
                    preferred_element_type=jnp.float32,
                ) * scale for q in qs
            ]  # (rows * Hs / parts, T) each
            news = [
                _online_softmax(s, k[:, :rank], *old, precision)
                for s, old in zip(ss, olds)
            ]
            for rows, new in zip(parts, news):
                for ref, value in zip(state, new):
                    ref[rows, :] = value

            def unstack(i, carry):
                r = member[i]
                m_p[r] = m_g[slot_of(i), :]
                l_p[r] = l_g[slot_of(i), :]
                acc_p[r] = acc_g[slot_of(i), :]

                # a row with no item of its own (every page shared and
                # whole) is finished by its last shared block
                @pl.when(
                    (blk + 1 == skip_ref[r])
                    & ((blk + 1) * P >= n_pages_ref[r])
                )
                def _():
                    o_ref[r] = (
                        acc_g[slot_of(i), :] / l_g[slot_of(i), :]
                    ).astype(o_ref.dtype)

                return carry

            lax.fori_loop(0, jnp.where(keep, 0, n), unstack, 0)
            return row

        lax.while_loop(lambda row: row < B, some_rows, first_row)
        return carry

    def body(item, carry):
        slot = (n_shared + item) % 2

        @pl.when(item + 1 < n_items)
        def _():
            page_copies(item + 1, 1 - slot, start)

        row = item_row_ref[item]
        blk = item_blk_ref[item]

        @pl.when(blk == skip_ref[row])
        def _():  # the row goes on from what its shared blocks left
            m_s[...] = m_p[row]
            l_s[...] = l_p[row]
            acc_s[...] = acc_p[row]

        page_copies(item, slot, wait)
        q = q_ref[row]  # (H, W)
        k = buf[slot]  # (T, W)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32,
        ) * scale  # (H, T)
        s = jnp.where(col < last_ref[row] - blk * T + 1, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # masked: exp(-1e30 - m) == 0 exactly
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = alpha * acc_s[...] + jnp.dot(
            p.astype(k.dtype), k[:, :rank], precision=precision,
            preferred_element_type=jnp.float32,
        )
        m_s[...] = m_new

        @pl.when((blk + 1) * P >= n_pages_ref[row])
        def _():
            o_ref[row] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)

        return carry

    lax.fori_loop(0, n_shared, shared_body, 0)
    lax.fori_loop(0, n_items, body, 0)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "interpret"))
def _latent_decode_device(q, pool, block_tables, lengths, *, scale, rank, interpret):
    """The latent decode kernel's call; a `jax.jit` of its own for the
    reason `_per_device` is one. The work list is plain XLA OUTSIDE the
    kernel's named scope (the layers' copies merge into one a step); under
    it there is the one call."""
    B, H, W = q.shape
    nblk, bs, _ = pool.shape
    nb = block_tables.shape[1]
    P = _pages_per_block(bs, nb)
    scalars = _work_list(block_tables, lengths, nblk, bs, P, share=True)
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    f32 = jnp.float32
    R = _latent_stacked_rows(H)
    # a stacked row's H heads sit in whole tiles of q's dtype and of float32
    tile = 32 // q.dtype.itemsize
    G = R * -(-H // tile) * tile
    with jax.named_scope("latent_decode_kernel"):
        return pl.pallas_call(
            functools.partial(
                _latent_decode_kernel, scale=scale, nb=nb, P=P, rank=rank
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars),
                grid=(1,),
                in_specs=[vmem(), pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=vmem(),
                scratch_shapes=[
                    pltpu.VMEM((2, P * bs, W), pool.dtype),
                    pltpu.SemaphoreType.DMA((1, 2)),
                    pltpu.VMEM((H, 1), f32),
                    pltpu.VMEM((H, 1), f32),
                    pltpu.VMEM((H, rank), f32),
                    # the stacked rows' queries and state; every row's
                    # waiting state; who is stacked
                    pltpu.VMEM((G, W), q.dtype),
                    pltpu.VMEM((G, 1), f32),
                    pltpu.VMEM((G, 1), f32),
                    pltpu.VMEM((G, rank), f32),
                    pltpu.VMEM((B, H, 1), f32),
                    pltpu.VMEM((B, H, 1), f32),
                    pltpu.VMEM((B, H, rank), f32),
                    pltpu.SMEM((R,), jnp.int32),
                    pltpu.SMEM((1,), jnp.int32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(pltpu.ARBITRARY,),
            ),
            interpret=interpret,
            name="latent_decode_attention",
        )(*scalars, q, pool)


def latent_decode_attention(
    q, pool, block_tables, lengths, scale, *, rank: int, interpret=None
):
    """One decode token a row against a paged LATENT pool, in the absorbed
    form of multi-head latent attention.

    q: (B, H, W), a head's query already moved onto the latent (`rank`
    values) beside its rotary part; pool: (num_blocks, bs, W), a token's
    normed latent beside its one rotated key; block_tables, lengths as
    `paged_decode_attention` takes them. Every head of row b scores the
    rows at positions <= lengths[b] over all W values and sums their
    leading `rank` values: returns (B, H, rank) in q's dtype, which the
    caller up-projects a head at a time. A page is copied once for both
    products, and a block that several rows' tables hold once for all of
    them (`shared_runs`; the module docstring has the two kinds of item).
    Callers check `paged_kernel` first; design, bounds and precision
    contract are `paged_decode_attention`'s."""
    if interpret is None:
        interpret = _interpret_default()
    return _latent_decode_device(
        q, pool, block_tables, lengths, scale=scale, rank=rank,
        interpret=interpret,
    )


def _latent_chunk_heads(H: int) -> tuple:
    """(heads of a grid step of the latent chunk kernel, how many of them one
    pass of its head loop takes) for H heads: the largest divisor of H within
    `LATENT_CHUNK_HEADS`, and `LATENT_CHUNK_CHAINS` of them a pass where that
    divides them."""
    hg = max(d for d in range(1, min(H, LATENT_CHUNK_HEADS) + 1) if H % d == 0)
    chains = max(d for d in range(1, LATENT_CHUNK_CHAINS + 1) if hg % d == 0)
    return hg, chains


def _latent_chunk_kernel(
    tables_ref, n_pages_ref, start_ref, q_ref, w_ref, pool_hbm, o_ref,
    buf, sems, k_s, wk_s, wvt_s, m_s, l_s, acc_s, *, scale, nb, P, rank, chains,
):
    """Grid step (b, i, g): queries `start[b] + i * bq ...` of row b with the
    `hg` heads of group g, transposed ((hg, dn + rope lanes, bq)), against
    every key block that holds a position <= the last of them, each block
    walked ONCE: its pages copied, then a head's keys and values made from
    the block's latents (`k = c W_uk` (T, dn) beside the block's one rotary
    key, `v^T = W_uv^T c^T` (dv, T)) in the pool's dtype, scored against
    the head's queries and summed. `_chunk_kernel` with the up-projection
    in the place of the strided read of a KV head. The group's weights come
    as the layer stores them, (r, hg * (dn + dv)) with a head's `W_uk`
    beside its `W_uv`, and are laid out a head once a step: `W_uk` (r, dn)
    as it is, `W_uv` transposed."""
    b, i = pl.program_id(0), pl.program_id(1)
    hg, _, bq = q_ref.shape
    dn, dv = wk_s.shape[2], wvt_s.shape[1]
    bs = pool_hbm.shape[1]
    T = P * bs  # keys a compute block
    precision = _precision(buf.dtype)
    first_q = start_ref[b] + i * bq
    n_pages = jnp.minimum(n_pages_ref[b], (first_q + bq + bs - 1) // bs)
    n_blocks = (n_pages + P - 1) // P
    dot = functools.partial(
        lax.dot_general, precision=precision,
        preferred_element_type=jnp.float32,
    )
    nn, nt = (((1,), (0,)), ((), ())), (((1,), (1,)), ((), ()))

    @pl.when((b == 0) & (i == 0) & (pl.program_id(2) == 0))
    def _():
        buf[...] = jnp.zeros_like(buf)  # see `_latent_decode_kernel`

    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)
    for h in range(hg):
        wk_s[h] = w_ref[:, h * (dn + dv):h * (dn + dv) + dn]
        wvt_s[h] = w_ref[:, h * (dn + dv) + dn:(h + 1) * (dn + dv)].T

    def page_copies(blk, slot, fn):
        _page_copies(
            fn, tables_ref, b * nb + blk * P, jnp.minimum(n_pages - blk * P, P),
            (pool_hbm,), (buf,), sems, slot,
        )

    @pl.when(n_blocks > 0)
    def _():
        page_copies(0, 0, lambda cp: cp.start())

    def attend(slot, key0, masked):
        """Scores TRANSPOSED, (keys, bq) a head, for the reasons
        `_chunk_kernel` gives. A pass of the head loop takes `chains` heads:
        their up-projections, then their independent chains (score product,
        column max, `exp`, value product), then every store, so that one
        head's softmax runs under another's products."""
        for n in range(chains):
            # the one rotary key of a token is every head's: lanes
            # `rank ...` of its row, beside the head's own keys
            k_s[n, :, dn:] = buf[slot, :, rank:]

        def heads(j, carry):
            h0 = j * chains
            c = buf[slot, :, :rank]  # (T, r): the block's latents
            vt = dot(
                wvt_s[pl.ds(h0, chains)].reshape(chains * dv, rank), c, nt
            ).astype(buf.dtype)  # (chains * dv, T): the heads' values
            for n in range(chains):
                k_s[n, :, :dn] = dot(c, wk_s[h0 + n], nn).astype(buf.dtype)
            olds = [
                (m_s[h0 + n], l_s[h0 + n], acc_s[h0 + n]) for n in range(chains)
            ]
            ss = [
                dot(k_s[n], q_ref[h0 + n], nn) * scale for n in range(chains)
            ]  # (T, bq) each
            if masked:
                key = lax.broadcasted_iota(jnp.int32, (T, bq), 0)
                t = lax.broadcasted_iota(jnp.int32, (T, bq), 1)
                keep = key <= jnp.minimum(first_q + t, n_pages * bs - 1) - key0
                ss = [jnp.where(keep, s, NEG_INF) for s in ss]
            news = []
            for n, (s, (m_prev, l_prev, acc_prev)) in enumerate(zip(ss, olds)):
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
                # masked: exp(-1e30 - m) == 0 exactly, key 0 is in every
                # query's first block so m is a score from there on
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)  # (1, bq)
                news.append((
                    m_new,
                    alpha * l_prev + jnp.sum(p, axis=0, keepdims=True),
                    alpha * acc_prev + dot(
                        vt[n * dv:(n + 1) * dv], p.astype(buf.dtype), nn
                    ),  # (dv, bq)
                ))
            for n, (m_new, l_new, acc_new) in enumerate(news):
                m_s[h0 + n], l_s[h0 + n], acc_s[h0 + n] = m_new, l_new, acc_new
            return carry

        lax.fori_loop(0, hg // chains, heads, 0)

    def body(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            page_copies(blk + 1, 1 - slot, lambda cp: cp.start())

        page_copies(blk, slot, lambda cp: cp.wait())
        key0 = blk * T
        # the mask is work: only a block that crosses the diagonal (a key
        # past the first query: the query block's own keys) or the row's
        # last valid page pays it
        masked = (key0 + T - 1 > first_q) | (key0 + T > n_pages * bs)

        @pl.when(masked)
        def _():
            attend(slot, key0, True)

        @pl.when(jnp.logical_not(masked))
        def _():
            attend(slot, key0, False)

        return carry

    lax.fori_loop(0, n_blocks, body, 0)

    def finish(h, carry):
        l = l_s[h]  # 0 on a row with no valid page: zeros, not 0 / 0
        o_ref[h] = jnp.where(l > 0, acc_s[h] / l, 0.0).astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, hg, finish, 0)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _latent_chunk_device(
    q_nope, q_rope, w_uk, w_uv, pool, block_tables, starts, *, scale, interpret
):
    """The latent chunk kernel's call; a `jax.jit` of its own for the
    reason `_per_device` is one. Around the call, as around
    `paged_chunk_attention`, the two XLA transposes that regroup the queries
    and the outputs by head; the weights go in as the layer stores them,
    a head's `W_uk` beside its `W_uv` (the two slices of `kv_b_proj` put
    together again: no copy in the layer's program)."""
    B, L, H, dn = q_nope.shape
    rank, _, dv = w_uv.shape
    nblk, bs, W = pool.shape
    nb = block_tables.shape[1]
    P = _pages_per_block(bs, nb)
    bq = min(L, CHUNK_QUERY_BLOCK)
    hg, chains = _latent_chunk_heads(H)
    block_tables = block_tables.astype(jnp.int32)
    scalars = (
        block_tables.reshape(B * nb), _leading(block_tables < nblk),
        starts.astype(jnp.int32),
    )
    # a head's query as the kernel scores it: [q_nope; q_rope; zeros] down
    # the sublanes, against [k_nope | the lanes of a pool row behind its
    # latent] (the rotary key, zeros where the pool holds wider rows)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    Dq = dn + W - rank
    q = jnp.pad(q, [(0, 0)] * 3 + [(0, Dq - q.shape[-1])]).astype(pool.dtype)
    qt = q.transpose(0, 2, 3, 1)  # (B, H, Dq, L)
    heads = lambda rows: pl.BlockSpec(
        (None, hg, rows, bq), lambda b, i, g, *_: (b, g, 0, i)
    )
    w = jnp.concatenate([w_uk, w_uv], axis=-1).astype(pool.dtype)
    w = w.reshape(rank, H * (dn + dv))
    f32 = jnp.float32
    with jax.named_scope("latent_chunk_kernel"):
        out = pl.pallas_call(
            functools.partial(
                _latent_chunk_kernel, scale=scale, nb=nb, P=P, rank=rank,
                chains=chains,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(scalars),
                grid=(B, L // bq, H // hg),
                in_specs=[
                    heads(Dq),
                    pl.BlockSpec(
                        (rank, hg * (dn + dv)), lambda b, i, g, *_: (0, g)
                    ),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=heads(dv),
                scratch_shapes=[
                    pltpu.VMEM((2, P * bs, W), pool.dtype),
                    pltpu.SemaphoreType.DMA((1, 2)),
                    pltpu.VMEM((chains, P * bs, Dq), pool.dtype),
                    pltpu.VMEM((hg, rank, dn), pool.dtype),
                    pltpu.VMEM((hg, dv, rank), pool.dtype),
                    pltpu.VMEM((hg, 1, bq), f32),
                    pltpu.VMEM((hg, 1, bq), f32),
                    pltpu.VMEM((hg, dv, bq), f32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((B, H, dv, L), q_nope.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=(pltpu.ARBITRARY,) * 3,
                vmem_limit_bytes=CHUNK_VMEM_BYTES,
            ),
            interpret=interpret,
            name="latent_chunk_attention",
        )(*scalars, qt, w, pool)
    return out.transpose(0, 3, 1, 2)  # (B, L, H, dv)


def latent_chunk_attention(
    q_nope, q_rope, w_uk, w_uv, pool, block_tables, starts, scale, *,
    interpret=None,
):
    """A prefill chunk of L query tokens a row against a paged LATENT
    pool, in the UP-PROJECTED form of multi-head latent attention: the
    layer as it is written, over the cache.

    q_nope: (B, L, H, dn) and q_rope: (B, L, H, dr), a head's query as the
    layer makes it (rotated, NOT moved onto the latent); w_uk: (r, H, dn)
    and w_uv: (r, H, dv), the layer's up-projections; pool, block_tables
    as `latent_decode_attention` takes them, the leading r values of a row
    its latent and the next dr its rotary key; starts: (B,) int32. Query i
    of row b sits at absolute position `starts[b] + i` and attends the rows
    at positions <= it (the chunk's own, written first, included); returns
    the heads' outputs themselves, (B, L, H, dv) in q_nope's dtype.

    A grid step takes a block of `CHUNK_QUERY_BLOCK` queries of a row (all
    L up to that many) with a GROUP of heads (`_latent_chunk_heads`) and
    walks the row's key blocks once: a block's pages are copied once a step,
    each head's keys and values are made from them in VMEM (one product a
    head and a block: every (key, head) is up-projected ONCE a query block),
    scored over dn + dr values and summed over dv. That is 2 r (dn + dv)
    FLOPs a key and head and 2 (dn + dr + dv) a pair where the absorbed
    form pays 2 (2 r + dr) a pair: less from r (dn + dv) / (2 r - dn - dv)
    queries a key up (171 at r 512, dn = dv 128), and the latents are read
    H / heads-a-group times a query block. `w_uk` and `w_uv` are the two
    halves of one (r, H, dn + dv) array in the layer, and the call puts
    them together again: XLA hands the kernel the stored matrix. Callers
    check `paged_kernel` first; design and precision contract in the module
    docstring."""
    if interpret is None:
        interpret = _interpret_default()
    return _latent_chunk_device(
        q_nope, q_rope, w_uk, w_uv, pool, block_tables, starts, scale=scale,
        interpret=interpret,
    )
