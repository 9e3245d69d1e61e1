"""`ops.paged_decode_attention` (the Pallas kernel, interpreted on the
CPU) against `ops.gather_paged_kv` + the dense einsum of
`Attention._decode_paged`, to the tolerance contract stated at the
kernel's definition: float32 reassociation in float32, bfloat16
rounding of scores and probabilities in bfloat16."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_distributed_example_tpu.ops import (
    gather_paged_kv,
    paged_attention,
    paged_decode_attention,
    paged_decode_ok,
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


def _facts(L, Dh, bs, KV, dtype, rows=32, entries=512):
    struct = jax.ShapeDtypeStruct
    return L, struct((64, bs, KV, Dh), dtype), struct((rows, entries), jnp.int32)


OK_CASES = {
    # name: ((L, Dh, bs, KV, pool dtype[, rows, table entries]), expected)
    "mistral_decode": ((1, 128, 16, 8, jnp.bfloat16), True),
    "float32_pool": ((1, 128, 16, 2, jnp.float32), True),
    "wide_head": ((1, 256, 16, 8, jnp.bfloat16), True),
    "prefill_chunk": ((512, 128, 16, 8, jnp.bfloat16), False),
    "two_tokens": ((2, 128, 16, 8, jnp.bfloat16), False),
    "tiny_head": ((1, 8, 4, 2, jnp.float32), False),
    "head_dim_64": ((1, 64, 16, 8, jnp.bfloat16), False),
    "int8_pool": ((1, 128, 16, 8, jnp.int8), False),
    "half_tile_page": ((1, 128, 4, 2, jnp.bfloat16), False),
    "one_tile_page_f32": ((1, 128, 4, 2, jnp.float32), True),
    # 128 x 1024 table entries are 512 KiB of scalar memory, 256 x 1024
    # the whole MiB (the v5e compiler refuses it: PERF.md, PR 25)
    "tables_fit_smem": ((1, 128, 16, 8, jnp.bfloat16, 128, 1024), True),
    "tables_exceed_smem": ((1, 128, 16, 8, jnp.bfloat16, 256, 1024), False),
}


@pytest.mark.parametrize("case", list(OK_CASES))
def test_the_predicate(case):
    facts, expected = OK_CASES[case]
    assert paged_decode_ok(*_facts(*facts)) is expected


def test_the_predicate_sees_the_kv_heads_one_device_holds():
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    facts = _facts(1, 128, 8, 2, jnp.bfloat16)  # 16 rows a page, 8 a shard
    assert paged_decode_ok(*facts)
    with partitioned_over(mesh, (), ("tp",)):
        assert not paged_decode_ok(*facts)
        assert paged_decode_ok(*_facts(1, 128, 16, 2, jnp.bfloat16))


def test_scale_defaults_to_the_head_size():
    lengths = [33, 2, 90]
    tables = tables_for(lengths)
    q, pool_k, pool_v = operands(jnp.float32, 8, 2)
    a = (q, pool_k, pool_v, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32))
    assert np.array_equal(
        np.asarray(paged_decode_attention(*a, interpret=True)),
        np.asarray(programs()[0](*a)),
    )
