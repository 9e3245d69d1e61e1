"""`ops.paged_decode_attention` and `ops.paged_chunk_attention` (the
Pallas kernels, interpreted on the CPU) against `ops.gather_paged_kv` +
the dense einsum of `Attention._decode_paged`, to the tolerance contract
stated at the kernels' definition: float32 reassociation in float32,
bfloat16 rounding of scores and probabilities in bfloat16."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_example_tpu.models.transformer import (
    _grouped_attention,
    _position_mask,
)
from pytorch_distributed_example_tpu.ops import (
    gather_paged_kv,
    paged_attention,
    paged_chunk_attention,
    paged_decode_attention,
    paged_kernel,
    partitioned_over,
)

DH, BS, NB, NBLK = 128, 16, 40, 160  # a table spans 640 keys: 2.5 blocks
SPAN = NB * BS
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}  # the contract's two limits
SCALE = DH ** -0.5


def dense_reference(q, pool_k, pool_v, tables, lengths):
    """The gather + einsum path of `_decode_paged` at L == 1, verbatim."""
    B, H, _ = q.shape
    KV = pool_k.shape[2]
    kf, vf = gather_paged_kv(pool_k, pool_v, tables)
    mask = jnp.arange(kf.shape[1])[None, None, :] <= lengths[:, None, None]
    qg = q.reshape(B, 1, KV, H // KV, DH)
    s = jnp.einsum("blkrd,bmkd->bkrlm", qg, kf) * SCALE
    s = jnp.where(mask[:, None, None], s.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(vf.dtype)
    return jnp.einsum("bkrlm,bmkd->blkrd", p, vf).reshape(B, H, DH)


@functools.lru_cache(maxsize=None)
def programs():
    """One jitted kernel and one jitted reference: lengths and tables are
    operands, so every case of a shape shares one compilation — which is
    also the property the engine needs (one step program for life)."""
    kernel = jax.jit(
        lambda *a: paged_decode_attention(*a, SCALE, interpret=True)
    )
    return kernel, jax.jit(dense_reference)


@functools.lru_cache(maxsize=None)
def operands(dtype, H, KV, B=3):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(H * 131 + KV), 3)
    shape = (NBLK, BS, KV, DH)
    return (
        jax.random.normal(k3, (B, H, DH), dtype),
        jax.random.normal(k1, shape, dtype),
        jax.random.normal(k2, shape, dtype),
    )


def tables_for(lengths, seed=0, shuffled=True, parked=()):
    """A table row holds exactly the pages its length needs, drawn
    without replacement; every other entry is the invalid sentinel."""
    rng = np.random.default_rng(seed)
    ids = rng.permutation(NBLK) if shuffled else np.arange(NBLK)
    tables = np.full((len(lengths), NB), NBLK, np.int32)
    at = 0
    for b, n in enumerate(lengths):
        if b in parked:
            continue
        m = n // BS + 1
        tables[b, :m] = ids[at:at + m]
        at += m
    return tables


def check(dtype, H, KV, lengths, tables, live=None):
    kernel, reference = programs()
    q, pool_k, pool_v = operands(dtype, H, KV, len(lengths))
    args = (q, pool_k, pool_v, jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))
    got = np.asarray(kernel(*args), np.float32)
    want = np.asarray(reference(*args), np.float32)
    assert got.shape == want.shape and np.isfinite(got).all()
    rows = list(range(len(lengths))) if live is None else live
    err = np.abs(got[rows] - want[rows]).max(initial=0.0)
    assert err <= TOL[dtype], (err, lengths)
    return got


DTYPES = [jnp.float32, jnp.bfloat16]
BLOCK = paged_attention.KEYS_PER_BLOCK
RAGGED = {
    "one_key": 0,  # attends position 0 alone
    "length_1": 1,
    "page_minus_1": BS - 1,
    "page": BS,
    "page_plus_1": BS + 1,
    "block_minus_1": BLOCK - 1,  # the last key of the first compute block
    "block": BLOCK,  # the first key of the second
    "several_blocks": 2 * BLOCK + 37,
    "full_span": SPAN - 1,
}


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(RAGGED))
def test_ragged_lengths_match_the_dense_path(dtype, case):
    """Row 0 at the named length, between a short and a long neighbour
    (a work list whose rows differ in their block counts)."""
    lengths = [RAGGED[case], 5, BLOCK + 3]
    check(dtype, 8, 2, lengths, tables_for(lengths, seed=len(case)))


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("shuffled", [False, True], ids=["in_order", "shuffled"])
def test_physical_block_order_does_not_matter(dtype, shuffled):
    lengths = [300, 17, 639]
    got = check(dtype, 8, 2, lengths, tables_for(lengths, 3, shuffled))
    assert np.abs(got).max() > 0.01


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [(32, 8), (8, 8), (8, 2)],
                         ids=["gqa_32_8", "mha_8_8", "gqa_8_2"])
def test_grouped_and_multi_head(dtype, heads):
    lengths = [BLOCK + 70, 31, 2]
    check(dtype, *heads, lengths, tables_for(lengths, seed=heads[0]))


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_parked_rows_beside_live_rows(dtype):
    """The engine parks retired, mid-prefill and frozen lanes at length
    M-1 over an all-invalid table row: live rows exact, parked rows
    finite (zeros: no work item, no page read)."""
    lengths = [SPAN - 1, 200, SPAN - 1]
    tables = tables_for(lengths, seed=5, parked=(0, 2))
    got = check(dtype, 8, 2, lengths, tables, live=[1])
    assert (got[[0, 2]] == 0).all()


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_every_row_parked(dtype):
    lengths = [SPAN - 1] * 3
    tables = tables_for(lengths, parked=(0, 1, 2))
    assert (check(dtype, 8, 2, lengths, tables, live=[]) == 0).all()


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_invalid_and_stale_entries_past_the_length_are_not_read(dtype):
    """Past the pages a row's length needs the table may hold the
    sentinel or a stale id; neither is attended (nor copied)."""
    lengths = [40, 300, 100]
    tables = tables_for(lengths, seed=9)
    tables[0, 3] = NBLK  # sentinel right after the row's 3 pages
    tables[0, 4:9] = 7  # stale ids behind it
    tables[2, 7:] = 11  # stale ids right after the row's 7 pages
    check(dtype, 8, 2, lengths, tables)


def test_a_row_is_bounded_by_its_leading_valid_entries():
    """A hole inside a row's length (the engine never makes one) ends
    the row there: the kernel attends the leading valid pages and reads
    nothing behind the hole."""
    lengths = [300, 100, 50]
    tables = tables_for(lengths, seed=2)
    tables[0, 5] = NBLK
    kernel, reference = programs()
    q, pool_k, pool_v = operands(jnp.float32, 8, 2)
    got = kernel(q, pool_k, pool_v, jnp.asarray(tables),
                 jnp.asarray(lengths, jnp.int32))
    cut = jnp.asarray([5 * BS - 1, 100, 50], jnp.int32)
    want = reference(q, pool_k, pool_v, jnp.asarray(tables), cut)
    assert np.abs(np.asarray(got - want)).max() <= TOL[jnp.float32]


@pytest.mark.parametrize("seed", range(3))
def test_work_list_names_only_valid_pages(seed):
    """What the kernel copies is what the list names: every (row, block,
    page) of it is a valid table entry, each needed page appears once,
    and a parked row has no item."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(0, SPAN, 6).tolist() + [SPAN - 1]
    tables = tables_for(lengths, seed, parked=(6,))
    tables[1, lengths[1] // BS + 1:] = 3  # stale ids past the length
    P = BLOCK // BS
    flat, n_pages, last, row, blk, n_items = map(
        np.asarray,
        paged_attention._work_list(
            jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), NBLK, BS, P
        ),
    )
    assert n_pages.tolist() == [n // BS + 1 for n in lengths[:6]] + [0]
    assert last.tolist() == lengths[:6] + [-1]
    seen = []
    for i in range(int(n_items[0])):
        for page in range(blk[i] * P, min((blk[i] + 1) * P, n_pages[row[i]])):
            assert flat[row[i] * NB + page] < NBLK
            seen.append((row[i], page))
    assert len(seen) == len(set(seen)) == n_pages.sum()
    assert 6 not in row[:int(n_items[0])]


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_two_device_shard_map_on_kv_heads(dtype):
    """Under `partitioned_over(mesh, (), ("tp",))` the kernel runs per
    device on its KV-head shard (q split on heads, tables and lengths
    replicated): same numbers as one device, output sharded on heads."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    lengths = [BLOCK + 9, 40, 3]
    tables = tables_for(lengths, seed=4, parked=(2,))
    q, pool_k, pool_v = operands(dtype, 8, 2)
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    args = (
        put(q, P(None, "tp", None)),
        put(pool_k, P(None, None, "tp", None)),
        put(pool_v, P(None, None, "tp", None)),
        put(jnp.asarray(tables), P()),
        put(jnp.asarray(lengths, jnp.int32), P()),
    )

    @jax.jit
    def sharded(*a):
        with partitioned_over(mesh, (), ("tp",)):
            return paged_decode_attention(*a, SCALE, interpret=True)

    assert "shard_map" in str(jax.make_jaxpr(sharded)(*args))
    got = sharded(*args)
    assert got.sharding.spec == P(None, "tp", None)
    want = programs()[0](q, pool_k, pool_v, jnp.asarray(tables),
                         jnp.asarray(lengths, jnp.int32))
    # shard-local matmul shapes may round the last bfloat16 bit otherwise
    same = 1e-6 if dtype == jnp.float32 else TOL[dtype]
    assert np.abs(np.asarray(got - want, np.float32)).max() <= same
    ref = programs()[1](q, pool_k, pool_v, jnp.asarray(tables),
                        jnp.asarray(lengths, jnp.int32))
    err = np.abs(np.asarray(got - ref, np.float32)[:2]).max()
    assert err <= TOL[dtype]


def test_heads_that_do_not_divide_the_axis_run_replicated():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]), ("tp",))
    lengths = [70, 3, 20]
    tables = tables_for(lengths, seed=1)
    q, pool_k, pool_v = operands(jnp.float32, 8, 2)
    with partitioned_over(mesh, (), ("tp",)):
        got = paged_decode_attention(
            q, pool_k, pool_v, jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32), SCALE, interpret=True,
        )
    want = programs()[1](q, pool_k, pool_v, jnp.asarray(tables),
                         jnp.asarray(lengths, jnp.int32))
    assert np.abs(np.asarray(got - want)).max() <= TOL[jnp.float32]


def _facts(L, Dh, bs, KV, dtype, rows=32, entries=512, window=None):
    struct = jax.ShapeDtypeStruct
    return (L, struct((64, bs, KV, Dh), dtype),
            struct((rows, entries), jnp.int32), window)


OK_CASES = {
    # name: ((L, Dh, bs, KV, pool dtype[, rows, table entries, window]),
    #        the kernel that takes the call)
    "mistral_decode": ((1, 128, 16, 8, jnp.bfloat16), "decode"),
    "float32_pool": ((1, 128, 16, 2, jnp.float32), "decode"),
    "wide_head": ((1, 256, 16, 8, jnp.bfloat16), "decode"),
    "window_decode": ((1, 128, 16, 8, jnp.bfloat16, 32, 512, 512), "decode"),
    "prefill_chunk": ((512, 128, 16, 8, jnp.bfloat16, 1), "chunk"),
    "smallest_bucket": ((128, 128, 16, 8, jnp.bfloat16, 1), "chunk"),
    "chunk_of_two_rows": ((256, 128, 16, 8, jnp.float32, 2), "chunk"),
    "one_tile_chunk_f32": ((8, 128, 8, 2, jnp.float32, 1), "chunk"),
    "half_tile_chunk_bf16": ((8, 128, 8, 2, jnp.bfloat16, 1), None),
    "two_tokens": ((2, 128, 16, 8, jnp.bfloat16), None),
    "not_whole_query_blocks": ((768, 128, 16, 8, jnp.bfloat16, 1), None),
    "two_query_blocks": ((1024, 128, 16, 8, jnp.bfloat16, 1), "chunk"),
    "window_chunk": ((512, 128, 16, 8, jnp.bfloat16, 1, 512, 512), None),
    "float16_chunk": ((512, 128, 16, 8, jnp.float16, 1), None),
    "float16_decode": ((1, 128, 16, 8, jnp.float16), "decode"),
    "odd_bf16_heads_chunk": ((512, 128, 16, 3, jnp.bfloat16, 1), None),
    "one_bf16_head_chunk": ((512, 128, 16, 1, jnp.bfloat16, 1), "chunk"),
    "tiny_head": ((1, 8, 4, 2, jnp.float32), None),
    "tiny_head_chunk": ((8, 8, 4, 2, jnp.float32, 1), None),
    "head_dim_64": ((1, 64, 16, 8, jnp.bfloat16), None),
    "head_dim_64_chunk": ((512, 64, 16, 8, jnp.bfloat16, 1), None),
    "int8_pool": ((1, 128, 16, 8, jnp.int8), None),
    "int8_pool_chunk": ((512, 128, 16, 8, jnp.int8, 1), None),
    "half_tile_page": ((1, 128, 4, 2, jnp.bfloat16), None),
    "half_tile_page_chunk": ((512, 128, 4, 2, jnp.bfloat16, 1), None),
    "one_tile_page_f32": ((1, 128, 4, 2, jnp.float32), "decode"),
    # 128 x 1024 table entries are 512 KiB of scalar memory, 256 x 1024
    # the whole MiB (the v5e compiler refuses it: PERF.md, PR 25)
    "tables_fit_smem": ((1, 128, 16, 8, jnp.bfloat16, 128, 1024), "decode"),
    "tables_exceed_smem": ((1, 128, 16, 8, jnp.bfloat16, 256, 1024), None),
    "chunk_tables_fit_smem": ((512, 128, 16, 8, jnp.bfloat16, 128, 1024), "chunk"),
    "chunk_tables_exceed_smem": ((512, 128, 16, 8, jnp.bfloat16, 256, 1024), None),
}


@pytest.mark.parametrize("case", list(OK_CASES))
def test_the_predicate(case):
    facts, expected = OK_CASES[case]
    assert paged_kernel(*_facts(*facts)) == expected


def test_the_predicate_sees_the_kv_heads_one_device_holds():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    facts = _facts(1, 128, 8, 2, jnp.bfloat16)  # 16 rows a page, 8 a shard
    chunk = _facts(16, 128, 16, 6, jnp.bfloat16)  # 6 heads, 3 a shard
    assert paged_kernel(*facts) == "decode"
    assert paged_kernel(*chunk) == "chunk"
    with partitioned_over(mesh, (), ("tp",)):
        assert paged_kernel(*facts) is None
        assert paged_kernel(*_facts(1, 128, 16, 2, jnp.bfloat16)) == "decode"
        assert paged_kernel(*chunk) is None
        assert paged_kernel(*_facts(16, 128, 16, 2, jnp.bfloat16)) == "chunk"


def test_scale_defaults_to_the_head_size():
    lengths = [33, 2, 90]
    tables = tables_for(lengths)
    q, pool_k, pool_v = operands(jnp.float32, 8, 2)
    a = (q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32))
    assert np.array_equal(
        np.asarray(paged_decode_attention(*a, interpret=True)),
        np.asarray(programs()[0](*a)),
    )


# -- the shared pass: a block several rows hold is read once -----------------

P16 = BLOCK // BS  # pages of a compute block
WIDE = dict(nb=144, nblk=420)  # tables that span 2304 keys: nine blocks


def share_tables(groups, lengths, nb=NB, nblk=NBLK, seed=0, parked=()):
    """`groups`: [(shared pages, rows)]: the rows of a group open with the
    same physical pages; every row then holds pages of its own up to its
    length. A parked row is all-invalid."""
    rng = np.random.default_rng(seed)
    ids = rng.permutation(nblk).tolist()
    tables = np.full((len(lengths), nb), nblk, np.int32)
    opened = {}
    for pages, rows in groups:
        head = [ids.pop() for _ in range(pages)]
        for r in rows:
            opened[r] = head
    for r, n in enumerate(lengths):
        if r in parked:
            continue
        m = n // BS + 1
        head = opened.get(r, [])[:m]
        tables[r, :m] = head + [ids.pop() for _ in range(m - len(head))]
    return tables


def work_list(tables, lengths, nblk=NBLK):
    """`_work_list`'s shared half: (skip a row, the shared list's (row,
    block) items, every item's rows as the kernel walks them)."""
    sc = paged_attention._work_list(
        jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), nblk, BS, P16,
        share=True,
    )
    assert len(sc) == 12
    skip, sh_row, off, n, nxt, _ = map(np.asarray, sc[6:])
    B, nbs = len(lengths), tables.shape[1] // P16
    items, members = [], []
    for i in range(int(n[0])):
        blk = i - int(off[sh_row[i]])
        items.append((int(sh_row[i]), blk))
        rows, r = [], int(sh_row[i])
        while r < B:
            rows.append(r)
            r = int(nxt[r * nbs + blk])
        members.append(rows)
    return skip.tolist(), items, members


@functools.lru_cache(maxsize=None)
def wide_operands(dtype, B):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(B), 3)
    shape = (WIDE["nblk"], BS, 2, DH)
    return (jax.random.normal(k3, (B, 8, DH), dtype),
            jax.random.normal(k1, shape, dtype), jax.random.normal(k2, shape, dtype))


def shared_check(dtype, lengths, tables, live=None, wide=False):
    """Kernel against the dense path on tables that share pages, and the
    host's count against the device's list."""
    if wide:
        kernel, reference = programs()
        q, pool_k, pool_v = wide_operands(dtype, len(lengths))
        args = (q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32))
        got = np.asarray(kernel(*args), np.float32)
        want = np.asarray(reference(*args), np.float32)
        rows = list(range(len(lengths))) if live is None else live
        assert np.isfinite(got).all()
        assert np.abs(got[rows] - want[rows]).max() <= TOL[dtype]
    else:
        check(dtype, 8, 2, lengths, tables, live)
    nblk = WIDE["nblk"] if wide else NBLK
    skip, items, members = work_list(tables, lengths, nblk)
    keys = paged_attention.shared_decode_keys(
        np.asarray(tables), np.asarray(lengths), nblk, BS)
    assert keys == sum(skip) * BLOCK == sum(len(m) for m in members) * BLOCK
    return skip, items, members


GROUPS = {"1": 1, "2": 2, "8": 8, "all_rows": 9}


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("size", list(GROUPS))
def test_a_group_reads_its_shared_blocks_once(dtype, size):
    """Nine rows, the first `size` of them behind one 512-key head (two
    whole compute blocks), every row with keys of its own behind it: a
    group of one shares nothing, the others are one item a block whose
    rows the kernel walks `SHARED_ROWS` at a time (eight: one full pass;
    nine: a second with one row)."""
    g = GROUPS[size]
    rng = np.random.default_rng(g)
    lengths = [int(n) for n in rng.integers(2 * BLOCK, SPAN, 9)]
    for r in range(g, 9):
        lengths[r] = int(rng.integers(0, 200))
    tables = share_tables([(2 * P16, range(g))], lengths, seed=g)
    skip, items, members = shared_check(dtype, lengths, tables)
    if g == 1:
        assert skip == [0] * 9 and not items
    else:
        assert skip == [2] * g + [0] * (9 - g)
        assert items == [(0, 0), (0, 1)] and members == [list(range(g))] * 2


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_two_groups_of_different_shared_lengths(dtype):
    """Rows 1, 3, 4 share two blocks, rows 0 and 5 one; row 2 nothing."""
    lengths = [300, 600, 77, 520, 639, 511]
    tables = share_tables([(2 * P16, (1, 3, 4)), (P16, (0, 5))], lengths, seed=3)
    skip, items, members = shared_check(dtype, lengths, tables)
    assert skip == [1, 2, 0, 2, 2, 1]
    assert items == [(0, 0), (1, 0), (1, 1)]
    assert members == [[0, 5], [1, 3, 4], [1, 3, 4]]


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_a_shared_run_that_ends_inside_a_compute_block(dtype):
    """129 pages shared (the benchmark's check: a 2056-token head): eight
    whole blocks are read once, the block the run ends in is each row's
    own, its first page the same physical page in every row."""
    lengths = [2100, 2303, 2064]
    tables = share_tables([(129, (0, 1, 2))], lengths, seed=1, **WIDE)
    assert len({int(t) for t in tables[:, 128]}) == 1
    skip, items, members = shared_check(dtype, lengths, tables, wide=True)
    assert skip == [8, 8, 8] and len(items) == 8
    assert all(m == [0, 1, 2] for m in members)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_a_row_whose_context_is_shared_but_for_the_token_it_writes(dtype):
    """Row 0 writes position 512, the first key of a page of its own
    behind two shared blocks; row 2's every page is shared and whole (its
    length is the head's last key), so it has no item of its own and its
    last shared block finishes it."""
    lengths = [2 * BLOCK, 2 * BLOCK + 90, 2 * BLOCK - 1]
    tables = share_tables([(2 * P16, (0, 1, 2))], lengths, seed=4)
    skip, items, members = shared_check(dtype, lengths, tables)
    assert skip == [2, 2, 2] and members == [[0, 1, 2]] * 2
    n_items = int(np.asarray(paged_attention._work_list(
        jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), NBLK, BS, P16,
        share=True)[5])[0])
    assert n_items == 2  # rows 0 and 1: one block of their own each


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_a_parked_member_and_one_that_ends_inside_the_run(dtype):
    """Of five rows behind one 512-key head, row 1 is parked (all-invalid,
    length M-1: no item at all) and row 3's length ends inside the second
    block: that block is not full for it, so it reads it as its own,
    masked at its length, and shares only the first."""
    lengths = [600, SPAN - 1, 639, BLOCK + 100, 530]
    tables = share_tables([(2 * P16, range(5))], lengths, seed=6, parked=(1,))
    skip, items, members = shared_check(dtype, lengths, tables, live=[0, 2, 3, 4])
    assert skip == [2, 0, 2, 1, 2]
    assert members == [[0, 2, 3, 4], [0, 2, 4]]


def continues(tables, lengths, nblk=NBLK):
    """`_work_list`'s flag an item of the shared list: 1 where the item's
    rows are the rows of the item before it, one block on."""
    sc = paged_attention._work_list(
        jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), nblk, BS, P16,
        share=True,
    )
    sh_row, off, n, cont = (np.asarray(sc[i]) for i in (7, 8, 9, 11))
    nbs = tables.shape[1] // P16
    return [
        int(cont[r * nbs + i - off[r]]) for i, r in enumerate(sh_row[:int(n[0])])
    ]


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_rows_stay_stacked_from_a_block_to_the_next_of_the_same_rows(dtype):
    """Three blocks behind rows 0, 1, 3 and the first two of them behind
    row 2 as well: the group's second item continues its first (the same
    four rows: their state stays stacked), the third does not (row 2 has
    left), and the pair behind rows 4 and 5 continues from its first
    block. A group of more rows than one stacked pass never continues."""
    lengths = [900, 2303, 1000, 800, 600, 639]
    wide = [(3 * P16, (0, 1, 3)), (2 * P16, (4, 5))]
    tables = share_tables(wide, lengths, seed=11, **WIDE)
    tables[2, :2 * P16] = tables[0, :2 * P16]
    skip, items, members = shared_check(dtype, lengths, tables, wide=True)
    assert skip == [3, 3, 2, 3, 2, 2]
    assert items == [(0, 0), (0, 1), (0, 2), (4, 0), (4, 1)]
    assert members == [[0, 1, 2, 3]] * 2 + [[0, 1, 3]] + [[4, 5]] * 2
    assert continues(tables, lengths, WIDE["nblk"]) == [0, 1, 0, 0, 1]
    many = paged_attention.SHARED_ROWS + 1
    lengths = [2 * BLOCK + 10 * r for r in range(many)]
    tables = share_tables([(2 * P16, range(many))], lengths, seed=12)
    assert continues(tables, lengths) == [0, 0]


def test_equal_entries_that_are_invalid_form_no_group():
    """Parked rows are equal everywhere (the sentinel), and two live rows
    are equal behind their lengths (sentinels, stale ids): no group."""
    lengths = [SPAN - 1, 300, SPAN - 1, 200]
    tables = tables_for(lengths, seed=2, parked=(0, 2))
    tables[1, 19:] = 7
    tables[3, 19:] = 7  # the same stale ids behind both rows' pages
    skip, items, _ = shared_check(jnp.float32, lengths, tables, live=[1, 3])
    assert skip == [0] * 4 and not items


@pytest.mark.parametrize("seed", range(4))
def test_the_host_sorts_and_the_device_pairs_to_one_rule(seed):
    """Tables no prefix cache would write: every block of a row is one of
    three candidates of its position (two apart, a third that parts from
    the first in its LAST page), lengths anywhere, stale ids or the
    sentinel behind them. Rows agree and part at any block, and agree
    again behind a block that differs, which shares nothing. The device's
    list (by pairs), the host's count (by sorting) and a count by hand
    agree, and the kernel attends what the dense path attends."""
    rng = np.random.default_rng(100 + seed)
    nb, nblk = WIDE["nb"], WIDE["nblk"]
    ids = rng.permutation(nblk).tolist()
    B, nbs = 7, nb // P16
    candidates = []
    for _ in range(nbs):
        a = [ids.pop() for _ in range(P16)]
        candidates.append([a, [ids.pop() for _ in range(P16)], a[:-1] + [ids.pop()]])
    tables = np.array([
        sum((candidates[j][rng.choice(3, p=[0.6, 0.2, 0.2])] for j in range(nbs)), [])
        for _ in range(B)
    ], np.int32)
    lengths = [int(n) for n in rng.integers(0, nb * BS, B)]
    lengths[0] = nb * BS - 1  # a row whose every block is full
    for r in range(0, B, 2):
        tables[r, lengths[r] // BS + 1:] = nblk
    full = [(n + 1) // BLOCK for n in lengths]
    blocks = tables.reshape(B, nbs, P16)
    want = []
    for r in range(B):
        runs = [0]
        for o in range(B):
            same = [bool((blocks[r, j] == blocks[o, j]).all())
                    for j in range(min(full[r], full[o]))]
            runs.append((same + [False]).index(False) if o != r else 0)
        want.append(max(runs))
    skip, _, members = shared_check(jnp.float32, lengths, tables, wide=True)
    assert skip == want and sum(want) > 0
    assert all(len(m) > 1 for m in members)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_without_sharing_the_kernel_is_the_group_free_kernel(dtype):
    """Tables in which no two rows hold one page: the shared list is empty
    and the output is bit-equal to the kernel traced without a shared pass
    (a window as long as the tables span attends the same keys)."""
    lengths = [2 * BLOCK + 37, 5, BLOCK + 3, SPAN - 1]
    tables = tables_for(lengths, seed=8)
    skip, items, _ = work_list(tables, lengths)
    assert skip == [0] * 4 and not items
    q, pool_k, pool_v = operands(dtype, 8, 2, 4)
    args = (q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32))
    got = paged_decode_attention(*args, SCALE, interpret=True)
    free = paged_decode_attention(*args, SCALE, interpret=True, window=SPAN)
    assert np.array_equal(np.asarray(got), np.asarray(free))
    assert np.abs(np.asarray(got, np.float32)).max() > 0.01


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_a_window_layer_does_not_share(dtype):
    """With a window the scalars are the eight they were (no shared list,
    every block in the (row, block) list) and rows that hold the same
    pages each read them: the output is that of rows holding copies."""
    lengths = [600, 639, 530]
    window = 2 * BLOCK
    shared = share_tables([(2 * P16, range(3))], lengths, seed=5)
    apart = shared.copy()
    q, pool_k, pool_v = operands(dtype, 8, 2)
    spare = [b for b in range(NBLK) if b not in set(shared.ravel().tolist())]
    for r in (1, 2):  # rows 1 and 2 get copies of the head's pages
        mine = [spare.pop() for _ in range(2 * P16)]
        pool_k = pool_k.at[jnp.asarray(mine)].set(pool_k[shared[0, :2 * P16]])
        pool_v = pool_v.at[jnp.asarray(mine)].set(pool_v[shared[0, :2 * P16]])
        apart[r, :2 * P16] = mine
    lens = jnp.asarray(lengths, jnp.int32)
    scalars = paged_attention._work_list(
        jnp.asarray(shared), lens, NBLK, BS, P16, window)
    assert len(scalars) == 8
    n_pages, n_items = np.asarray(scalars[1]), int(np.asarray(scalars[5])[0])
    assert n_items == sum(-(-int(n) // P16) for n in n_pages)
    run = lambda t: np.asarray(paged_decode_attention(
        q, pool_k, pool_v, jnp.asarray(t), lens, SCALE, interpret=True,
        window=window))
    assert np.array_equal(run(shared), run(apart))
    # and without a window the same rows do share, to the same numbers
    both = [np.asarray(paged_decode_attention(
        q, pool_k, pool_v, jnp.asarray(t), lens, SCALE, interpret=True), np.float32)
        for t in (shared, apart)]
    assert work_list(shared, lengths)[0] == [2, 2, 2]
    assert work_list(apart, lengths)[0] == [0, 0, 0]
    assert np.abs(both[0] - both[1]).max() <= TOL[dtype]


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("H,KV", [(8, 4), (8, 8), (4, 1)], ids=["rep2", "rep1", "one_kv_head"])
def test_a_shared_block_by_the_heads_a_word_holds(dtype, H, KV):
    """A shared block is read by the KV heads one 32-bit row of a page
    holds (one of float32, two of bfloat16): several such reads a block
    at four and eight KV heads, a query group of two and of one, and the
    whole page at once where there is one KV head."""
    lengths = [600, 639, 530, 40]
    tables = share_tables([(2 * P16, range(3))], lengths, seed=H + KV)
    check(dtype, H, KV, lengths, tables)
    assert work_list(tables, lengths)[0] == [2, 2, 2, 0]


@pytest.mark.parametrize("dtype,shares", [
    (jnp.float32, True), (jnp.bfloat16, False), (jnp.float16, False),
], ids=["f32", "bf16", "f16"])
def test_heads_the_kernel_cannot_separate_do_not_share(dtype, shares):
    """Three KV heads: float32 pages separate by strided rows and share;
    bfloat16 heads do not fill 32-bit words and float16 is not read by
    words, so those pools take the (row, block) items alone (six scalars)
    and rows that hold one head each read it, to the same numbers."""
    lengths = [600, 639, 530]
    tables = share_tables([(2 * P16, range(3))], lengths, seed=9)
    pool = jax.ShapeDtypeStruct((NBLK, BS, 3, DH), dtype)
    assert paged_attention.decode_shares(pool) is shares
    assert not paged_attention.decode_shares(pool, window=BLOCK)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(k3, (3, 6, DH), dtype)
    pool_k, pool_v = (jax.random.normal(k, pool.shape, dtype) for k in (k1, k2))
    args = (q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32))
    got = np.asarray(paged_decode_attention(*args, SCALE, interpret=True), np.float32)
    want = np.asarray(dense_reference(*args), np.float32)
    assert np.abs(got - want).max() <= TOL.get(dtype, 2e-2)


def test_the_shared_pass_asks_for_the_vmem_its_rows_state_takes():
    """Every row's waiting (max, sum, accumulator) is scratch that grows
    with the rows: within what a kernel gets unasked at the serve cells'
    32 rows, past it at 256, where the call asks for what it holds
    (compiled for a v5e at both: `tests/test_aot_topology.py`)."""
    ask = lambda B: paged_attention._shared_vmem_limit(B, 32, 128, 2048, 2)
    assert ask(32) == 16 << 20
    state = 32 * (128 * 4 + 2 * 512)  # a row: the max and the sum a lane tile a head
    assert ask(512) - ask(256) == 256 * (state + 2 * 32 * 128 * 2)  # and q, o
    assert 16 << 20 < ask(256) < 32 << 20


def test_the_predicate_counts_the_shared_list_s_scalars():
    """The shared list rides in scalar memory beside the (row, block)
    list: tables that fit with it take the kernel, wider ones the gather
    path (and a window layer, which has no shared list, still fits)."""
    fits = _facts(1, 128, 16, 8, jnp.bfloat16, 144, 1024)
    assert paged_kernel(*fits) == "decode"
    over = _facts(1, 128, 16, 8, jnp.bfloat16, 152, 1024)
    assert paged_kernel(*over) is None
    assert paged_kernel(*over[:3], 512) == "decode"


# -- the chunk kernel: L query tokens a row ---------------------------------


def chunk_reference(q, pool_k, pool_v, tables, starts):
    """The gather + einsum path of `_decode_paged` at L > 1, by its own
    functions."""
    B, L, H, _ = q.shape
    kf, vf = gather_paged_kv(pool_k, pool_v, tables)
    pos = starts[:, None] + jnp.arange(L)[None, :]
    mask = _position_mask(pos, jnp.arange(kf.shape[1])[None], None)
    return _grouped_attention(q, kf, vf, SCALE, mask).reshape(B, L, H, DH)


@functools.lru_cache(maxsize=None)
def chunk_programs():
    kernel = jax.jit(
        lambda *a: paged_chunk_attention(*a, SCALE, interpret=True)
    )
    return kernel, jax.jit(chunk_reference)


@functools.lru_cache(maxsize=None)
def chunk_operands(dtype, H, KV, B, L):
    _, pool_k, pool_v = operands(dtype, H, KV)
    q = jax.random.normal(jax.random.PRNGKey(H + L), (B, L, H, DH), dtype)
    return q, pool_k, pool_v


def chunk_tables(starts, L, seed=0, shuffled=True, tokens=None):
    """Row b holds the pages of positions < starts[b] + tokens[b] (the
    whole chunk unless a padded final chunk is asked for)."""
    ends = [s + (L if tokens is None else tokens[b]) - 1
            for b, s in enumerate(starts)]
    return tables_for(ends, seed, shuffled)


def chunk_check(dtype, H, KV, L, starts, tables, real=None):
    """Kernel against reference on the rows (b, :real[b]) a prompt holds;
    every row finite."""
    kernel, reference = chunk_programs()
    q, pool_k, pool_v = chunk_operands(dtype, H, KV, len(starts), L)
    args = (q, pool_k, pool_v, jnp.asarray(tables),
            jnp.asarray(starts, jnp.int32))
    got = np.asarray(kernel(*args), np.float32)
    want = np.asarray(reference(*args), np.float32)
    assert got.shape == want.shape and np.isfinite(got).all()
    for b in range(len(starts)):
        n = L if real is None else real[b]
        err = np.abs(got[b, :n] - want[b, :n]).max(initial=0.0)
        assert err <= TOL[dtype], (err, b, starts)
    return got


KEYS = BLOCK  # the chunk kernel walks the same key blocks
assert BS < KEYS < SPAN  # the cases below cross a page and a key block
CHUNKS = {
    # name: (H, KV, L, starts): rep 4 is 32/8 and 8/2, rep 6 is 48/8
    "start_0": (8, 2, 128, [0, 0]),
    "start_inside_a_page": (8, 2, 128, [37, BS + 1]),
    "start_inside_a_key_block": (8, 2, 128, [KEYS - 200, 3 * BS]),
    "chunk_crosses_a_key_block": (8, 2, 128, [KEYS - 40, KEYS - 127]),
    "chunk_starts_at_a_key_block": (8, 2, 128, [KEYS, 0]),
    "rows_at_different_depths": (32, 8, 128, [5, KEYS - 32]),
    "rep_6_512": (48, 8, 512, [77]),
    "one_query_block_rep_6": (48, 8, 256, [KEYS - 150]),
    "three_buckets_512": (32, 8, 512, [100]),
    "three_buckets_256": (32, 8, 256, [SPAN - 256]),
}


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CHUNKS))
def test_chunk_matches_the_dense_path(dtype, case):
    H, KV, L, starts = CHUNKS[case]
    chunk_check(dtype, H, KV, L, starts, chunk_tables(starts, L, len(case)))


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_chunk_of_two_query_blocks(dtype):
    """Longer than `CHUNK_QUERY_BLOCK` (an unchunked prefill's bucket):
    the second grid step's queries start where the first's end, and the
    first visits no key block past its own last query."""
    L = 2 * paged_attention.CHUNK_QUERY_BLOCK
    starts = [100]
    assert SPAN < starts[0] + L <= NBLK * BS
    tables = np.full((1, 2 * NB), NBLK, np.int32)
    pages = -(-(starts[0] + L) // BS)
    tables[0, :pages] = np.random.default_rng(5).permutation(NBLK)[:pages]
    chunk_check(dtype, 8, 2, L, starts, tables)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("shuffled", [False, True], ids=["in_order", "shuffled"])
def test_chunk_physical_block_order_does_not_matter(dtype, shuffled):
    starts = [300, 17]
    got = chunk_check(
        dtype, 8, 2, 128, starts, chunk_tables(starts, 128, 3, shuffled)
    )
    assert np.abs(got).max() > 0.01


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_chunk_padded_past_the_prompt(dtype):
    """The last chunk of a prompt is padded to its bucket and the table
    holds the prompt's pages alone: the prompt's queries are exact, the
    padded ones (whose own positions have no page) finite."""
    starts, tokens = [KEYS - 60, 40], [70, 3]
    tables = chunk_tables(starts, 128, seed=6, tokens=tokens)
    assert (tables[0, (starts[0] + 70 - 1) // BS + 1:] == NBLK).all()
    chunk_check(dtype, 8, 2, 128, starts, tables, real=tokens)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_chunk_reads_no_page_past_the_chunk(dtype):
    """Past the pages of `start + L` positions the table may hold stale
    ids: poison every page the chunk does not need and nothing moves."""
    starts, L = [KEYS - 100, 20], 128
    tables = chunk_tables(starts, L, seed=8)
    needed = tables[tables < NBLK]
    tables[0, (starts[0] + L - 1) // BS + 1:] = 7  # stale ids behind it
    tables[1, (starts[1] + L - 1) // BS + 2:] = 11  # a hole, then stale ids
    q, pool_k, pool_v = chunk_operands(dtype, 8, 2, 2, L)
    poison = np.ones((NBLK, 1, 1, 1), bool)
    poison[needed] = False
    args = (jnp.asarray(tables), jnp.asarray(starts, jnp.int32))
    kernel, reference = chunk_programs()
    got = kernel(q, jnp.where(poison, jnp.nan, pool_k),
                 jnp.where(poison, jnp.nan, pool_v), *args)
    want = reference(q, pool_k, pool_v, *args)
    err = np.abs(np.asarray(got - want, np.float32)).max()
    assert err <= TOL[dtype]


def test_chunk_row_is_bounded_by_its_leading_valid_entries():
    """A row with no valid page returns zeros; a hole inside the chunk's
    span (the engine never makes one) ends the row there."""
    starts, L = [200, 100], 128
    tables = chunk_tables(starts, L, seed=2)
    tables[0] = NBLK
    hole = 9  # row 1 keeps positions < 9 * BS = 144 of its 228
    tables[1, hole] = NBLK
    kernel, reference = chunk_programs()
    q, pool_k, pool_v = chunk_operands(jnp.float32, 8, 2, 2, L)
    a = (jnp.asarray(tables), jnp.asarray(starts, jnp.int32))
    got = np.asarray(kernel(q, pool_k, pool_v, *a))
    assert (got[0] == 0).all() and np.isfinite(got).all()
    want = np.asarray(reference(q, pool_k, pool_v, *a))
    n = hole * BS - starts[1]  # queries whose every key is before the hole
    assert np.abs(got[1, :n] - want[1, :n]).max() <= TOL[jnp.float32]


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_chunk_two_device_shard_map_on_kv_heads(dtype):
    """As the decode kernel: per device on its KV-head shard (one head of
    two here, so each shard's pages need no de-interleaving)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    starts, L = [KEYS - 70, 9], 128
    tables = chunk_tables(starts, L, seed=4)
    q, pool_k, pool_v = chunk_operands(dtype, 8, 2, 2, L)
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    heads = P(None, None, "tp", None)
    args = (
        put(q, heads), put(pool_k, heads), put(pool_v, heads),
        put(jnp.asarray(tables), P()), put(jnp.asarray(starts, jnp.int32), P()),
    )

    @jax.jit
    def sharded(*a):
        with partitioned_over(mesh, (), ("tp",)):
            return paged_chunk_attention(*a, SCALE, interpret=True)

    assert "shard_map" in str(jax.make_jaxpr(sharded)(*args))
    got = sharded(*args)
    assert got.sharding.spec == heads
    plain = (q, pool_k, pool_v, jnp.asarray(tables),
             jnp.asarray(starts, jnp.int32))
    want = chunk_programs()[1](*plain)
    assert np.abs(np.asarray(got - want, np.float32)).max() <= TOL[dtype]
    one = chunk_programs()[0](*plain)
    same = 1e-6 if dtype == jnp.float32 else TOL[dtype]
    assert np.abs(np.asarray(got - one, np.float32)).max() <= same


def test_chunk_scale_defaults_to_the_head_size():
    starts, L = [33, 2], 128
    q, pool_k, pool_v = chunk_operands(jnp.float32, 8, 2, 2, L)
    a = (q, pool_k, pool_v, jnp.asarray(chunk_tables(starts, L)),
         jnp.asarray(starts, jnp.int32))
    assert np.array_equal(
        np.asarray(paged_chunk_attention(*a, interpret=True)),
        np.asarray(chunk_programs()[0](*a)),
    )
