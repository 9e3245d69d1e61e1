"""Paged decode attention — one Pallas TPU kernel over the serve block pool.

The decode step's attention (`models/transformer.py::Attention.
_decode_paged` at L == 1) reads each row's K/V pages STRAIGHT out of the
shared block pool (`serve/cache.py`) and runs an online softmax over
them, so a row costs the pages it has — not the `nb * bs` keys its
table could address, which is what `gather_paged_kv` + the dense einsum
move (that pair stays the path of prefill chunks, int8 pools and shapes
Mosaic cannot tile: `paged_decode_ok` is the one predicate).

Shape of the kernel (design per /opt/skills/guides/pallas_guide.md):

* ONE program, static shapes. Block tables, each row's page count and
  last attended position, and a flat WORK LIST of (row, compute block)
  items ride in as scalar prefetch (SMEM); the list is as long as the
  live pages need, so the trip count — not a shape — follows the
  lengths, and nothing recompiles when they change.
* The pools stay in HBM (`memory_space=ANY`), viewed as
  (num_blocks, bs * KV, Dh): a page of all KV heads is one contiguous
  DMA. A compute block is `pages_per_block` pages copied, as many as the
  row has there, into one of two VMEM buffers; item i+1's pages (the
  next row's first block included) are in flight while item i computes.
* A row is bounded by its LEADING VALID table entries as well as its
  length: a parked lane (all-invalid table row, length M-1) has no work
  item, reads no page and returns zeros; an invalid entry past a live
  row's length is never read.
* A WINDOW (`window=`, a layer that attends the last `window` keys):
  the row's first page is the one that holds its first attended key
  (`page0`), keys before that key in the page are masked (`lo`), and the
  row costs min(length, window) keys. Entries before `page0` are never
  read and may be invalid (the serve cache frees them while the request
  lives). Without a window the scalars, the body and the compiled
  kernel are what they were before windows existed.
* All query heads meet all KV heads of a page in one MXU call: scores
  are (H, keys * KV) with column c = key * KV + kv_head, and the columns
  of another group's KV head are masked like keys past the length. That
  spends KV times the needed MXU work on a memory-bound step instead of
  strided sub-tile loads of single heads out of a packed page.

Tolerance contract (tests/test_paged_attention.py tests to it). Scores
and the running max / sum are float32, probabilities are cast to the
value dtype before the value product, the accumulator is float32 and
the output is cast once — the dense path's recipe, with two
differences that both err on the side of precision: scores are NOT
rounded to the pool dtype before the softmax (the dense einsum's output
is), and normalisation happens after the value product. Against
`gather_paged_kv` + the dense einsum on the same operands the kernel
therefore agrees to float32 reassociation in float32 (max abs error
<= 2e-5 at unit-scale inputs) and to bfloat16 rounding of scores and
probabilities in bfloat16 (max abs error <= 2e-2 on outputs of unit
scale). No lower precision, no approximation, no truncated span.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._compat import shard_map_fn
from .flash_attention import NEG_INF, _interpret_default, _partition

#: keys of one compute block (pages_per_block = KEYS_PER_BLOCK // bs):
#: 256 read 566 GB/s of live K/V at the decode cell's depths on a v5e,
#: 512 the same (555), 128 less (475) — PERF.md, PR 25.
KEYS_PER_BLOCK = 256
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


def paged_decode_ok(L: int, pool, block_tables) -> bool:
    """Whether `paged_decode_attention` takes this call — THE predicate,
    read by `Attention._decode_paged` (which path to trace) and by
    `serve.decode.step_runs_kernel` (which path the engine's step
    counter names), from what both can see: the query length, the K
    pool and the block tables (arrays or `ShapeDtypeStruct`s; shapes
    and dtype alone are read), and the `partitioned_over` context a tp
    engine's programs apply the model under.

    One query token a row (decode, not a prefill chunk); a floating
    pool of 2 or 4 bytes (the int8 pool dequantises in the gather); `Dh`
    a multiple of the 128 lanes; a page whose `bs * KV` rows of `Dh` (KV
    as one device holds it) fill whole sublane tiles of the pool dtype
    (8 rows of float32, 16 of bfloat16), so page copies land
    tile-aligned in the VMEM buffer; tables and work list (with the two
    scalars a row that a window layer adds) within the scalar memory
    they are prefetched into."""
    _, bs, KV, Dh = pool.shape
    B, nb = block_tables.shape
    itemsize = jnp.dtype(pool.dtype).itemsize
    if not jnp.issubdtype(pool.dtype, jnp.floating) or itemsize not in (2, 4):
        return False
    rows = bs * (KV // _head_shards(KV))
    items = B * -(-nb // _pages_per_block(bs, nb))
    return (
        L == 1
        and Dh % 128 == 0
        and rows % (32 // itemsize) == 0
        and 4 * (B * nb + 2 * items + 4 * B + 1) <= SMEM_BYTES
    )


def _work_list(block_tables, lengths, nblk, bs, P, window=None):
    """Scalar side of the kernel, in plain XLA (tiny, identical in every
    layer of a kind in a step, so the compiler keeps one copy): per row
    the pages to read — bounded by the length AND by the leading valid
    entries — and the last position attended; then the flat (row, block)
    list. With a `window`, also each row's first page `page0` (pages are
    counted from it, entries before it count as valid whatever they
    hold) and `lo`, the first attended key's offset in that page."""
    B, nb = block_tables.shape
    block_tables = block_tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    valid = block_tables < nblk
    if window is not None:
        first = jnp.clip(lengths - (window - 1), 0, nb * bs - 1)
        page0 = first // bs
        valid |= jnp.arange(nb)[None, :] < page0[:, None]
    lead = jnp.sum(jnp.cumprod(valid, axis=1, dtype=jnp.int32), axis=1)
    n_pages = jnp.minimum(lead, jnp.clip(lengths // bs + 1, 0, nb))
    last = jnp.minimum(lengths, n_pages * bs - 1)  # -1 on a parked row
    if window is not None:
        n_pages = jnp.maximum(n_pages - page0, 0)  # from page0 on
        last = last - page0 * bs  # relative to page0's first key
        extra = (page0, first - page0 * bs)
    n_blocks = (n_pages + P - 1) // P
    ends = jnp.cumsum(n_blocks)
    ids = jnp.arange(B * -(-nb // P), dtype=jnp.int32)
    row = jnp.sum(ids[:, None] >= ends[None, :], axis=1, dtype=jnp.int32)
    row = jnp.minimum(row, B - 1)  # ids past the list: never read
    blk = ids - (ends - n_blocks)[row]
    scalars = (block_tables.reshape(B * nb), n_pages, last, row, blk, ends[-1:])
    return scalars if window is None else scalars + extra


def _kernel(*refs, scale, nb, P, KV, windowed):
    # scalar prefetch (two more with a window), inputs, output, scratch
    (tables_ref, n_pages_ref, last_ref, item_row_ref, item_blk_ref,
     n_items_ref) = refs[:6]
    page0_ref, lo_ref = refs[6:8] if windowed else (None, None)
    (q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, colpos, m_s, l_s,
     acc_s) = refs[8 if windowed else 6:]
    H = q_ref.shape[1]
    rows = k_hbm.shape[1]  # bs * KV rows of Dh a page
    R = P * rows
    T = R // KV  # keys a compute block
    rep = H // KV
    n_items = n_items_ref[0]
    # bfloat16 products are exact in one MXU pass; float32 pools take the
    # multi-pass product. Named here so that an ambient
    # `jax_default_matmul_precision` (the test harness pins "highest")
    # cannot ask Mosaic for a float32 contraction of bfloat16 operands.
    precision = (
        lax.Precision.HIGHEST if kbuf.dtype == jnp.float32
        else lax.Precision.DEFAULT
    )

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

    def page_copies(item, slot, fn):
        """Apply `fn` (start or wait) to the K and V copy of every page
        compute block `item` has."""
        row = item_row_ref[item]
        first = item_blk_ref[item] * P
        have = jnp.minimum(n_pages_ref[row] - first, P)
        if windowed:
            first = first + page0_ref[row]

        def one(i, carry):
            page = tables_ref[row * nb + first + i]
            dst = pl.ds(pl.multiple_of(i * rows, rows), rows)
            for pool, buf, s in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                fn(pltpu.make_async_copy(
                    pool.at[page], buf.at[slot, dst], sems.at[s, slot]
                ))
            return carry

        lax.fori_loop(0, have, one, 0)

    @pl.when(n_items > 0)
    def _():
        page_copies(0, 0, lambda cp: cp.start())

    def body(item, carry):
        slot = item % 2

        @pl.when(item + 1 < n_items)
        def _():
            page_copies(item + 1, 1 - slot, lambda cp: cp.start())

        row = item_row_ref[item]
        blk = item_blk_ref[item]

        @pl.when(blk == 0)
        def _():
            m_s[...] = jnp.full_like(m_s, NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        page_copies(item, slot, lambda cp: cp.wait())
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

    lax.fori_loop(0, n_items, body, 0)


@functools.partial(jax.jit, static_argnames=("scale", "interpret", "window"))
def _per_device(
    q, pool_k, pool_v, block_tables, lengths, *, scale, interpret, window=None
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
    scalars = _work_list(block_tables, lengths, nblk, bs, P, window)
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = lambda: pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, nb=nb, P=P, KV=KV, windowed=window is not None
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(1,),
            in_specs=[vmem(), hbm(), hbm()],
            out_specs=vmem(),
            scratch_shapes=[
                pltpu.VMEM((2, P * rows, Dh), pool_k.dtype),
                pltpu.VMEM((2, P * rows, Dh), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((H, P * rows), jnp.int32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.ARBITRARY,),
        ),
        interpret=interpret,
        name="paged_decode_attention",
    )(
        *scalars, q,
        pool_k.reshape(nblk, rows, Dh), pool_v.reshape(nblk, rows, Dh),
    )


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

    Callers check `paged_decode_ok` first; precision contract in the
    module docstring.
    """
    Dh = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    if interpret is None:
        interpret = _interpret_default()
    local = functools.partial(
        _per_device, scale=scale, interpret=interpret, window=window
    )
    if _partition.spec is None:
        return local(q, pool_k, pool_v, block_tables, lengths)
    # rows stay whole on every device (a serve step shards no batch axis)
    jmesh, _, head_axes = _partition.spec
    P = jax.sharding.PartitionSpec
    h = head_axes if _head_shards(pool_k.shape[2]) > 1 else None
    pool = P(None, None, h, None)
    return shard_map_fn(
        local, jmesh, (P(None, h, None), pool, pool, P(), P()),
        P(None, h, None),
    )(q, pool_k, pool_v, block_tables, lengths)
