"""The seam a kind of layer is registered at: `models/transformer.py::
cache_leaves` (what its mixer keeps) and its record in `serve/kinds.py` (what
the serve plane does with that). One parametrised test a property, a case a
kind: the five and the plain model of full layers alone. What the answers are
compared with is the parent's (PR 45), written out here."""

import ast
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_example_tpu.models.generate import init_cache
from pytorch_distributed_example_tpu.models.transformer import (
    CACHE_KINDS,
    STATE_KINDS,
    LayerSpec,
    RopeSpec,
    TransformerConfig,
    TransformerLM,
    cache_leaves,
    layers_of,
    state_block_shapes,
)
from pytorch_distributed_example_tpu.serve import ServeEngine
from pytorch_distributed_example_tpu.serve.cache import PagedKVCache, init_paged_cache
from pytorch_distributed_example_tpu.serve.decode import (
    layer_paths, paged_programs, step_shares_blocks,
)
from pytorch_distributed_example_tpu.serve.kinds import FAMILIES, KINDS, kinds_of

BS, M = 8, 64
SERVE = Path(__file__).resolve().parents[1] / "pytorch_distributed_example_tpu" / "serve"

# a case: the layers of a small model, every size of every mixer set
CASES = {
    "plain": None,
    "full": ("full", "full"),
    "window": ("full", "window"),
    "linear": ("linear", "full", "linear"),
    "latent": ("latent", "latent"),
    "conv": ("conv", "full"),
    "all_five": ("conv", "full", "window", "linear", "latent", "linear"),
}


def model_of(case, **sizes):
    layers = CASES[case]
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, d_ff=64, max_seq_len=M,
        n_layers=2 if layers is None else len(layers), use_flash=False, window=8,
        linear_heads=2, linear_key_dim=8, linear_value_dim=12, latent_q_rank=8,
        latent_kv_rank=16, latent_nope_dim=8, latent_rope_dim=4, latent_v_dim=8,
        conv_taps=3, layers=layers and tuple(LayerSpec(kind) for kind in layers),
        **sizes)
    return TransformerLM(cfg)


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    model = model_of(request.param)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return request.param, model, variables


def spec_of(tree):
    return jax.tree_util.tree_map(lambda a: (tuple(a.shape), str(a.dtype)), tree)


def test_one_record_a_kind_in_the_model_s_order():
    assert tuple(KINDS) == CACHE_KINDS == ("full", "window", "linear", "latent", "conv")
    assert all(kind.table in FAMILIES and kind.name == name for name, kind in KINDS.items())
    # the kinds that keep one entry a row are the ones that ride the state table
    rows = tuple(k for k in CACHE_KINDS if cache_leaves(model_of("all_five").cfg, k)[1] == "row")
    assert rows == STATE_KINDS == tuple(k for k in CACHE_KINDS if KINDS[k].table == "state")
    assert all(KINDS[k].masks_padding == (k in STATE_KINDS) for k in CACHE_KINDS)


# --- (a) the leaves are the mixer's own, and the pool's are made from them ----

def test_cache_leaves_gives_the_tree_the_model_builds(case):
    name, model, _ = case
    cfg, B = model.cfg, 2
    want = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((B, 1), jnp.int32), decode=True))["cache"]
    assert layers_of(cfg) == (CASES[name] or ("full", "full"))
    assert kinds_of(cfg) == tuple(KINDS[k] for k in cfg.cache_kinds)
    built = {}
    for i, kind in enumerate(layers_of(cfg)):
        mixer, span, leaves = cache_leaves(cfg, kind)
        assert span in ("tokens", "window", "row")
        lead = (B,) if span == "row" else (B, M)
        layer = {leaf: (lead + shape, str(jnp.dtype(dtype))) for leaf, (shape, dtype) in leaves.items()}
        if span != "row":
            layer["index"] = ((), "int32")
        built[f"layers_{i}"] = {mixer: layer}
        if kind in STATE_KINDS:
            assert state_block_shapes(cfg, kind) == (mixer, leaves)
    assert built == spec_of(want) == spec_of(init_cache(model, B))


def test_init_paged_cache_gives_the_pool_the_programs_take_and_give_back(case):
    _, model, variables = case
    cfg = model.cfg
    blocks = {"blocks": 16, "window": 6, "state": 3}
    tree = init_paged_cache(model, 16, BS, window_blocks=6, state_blocks=3)
    for i, kind in enumerate(layers_of(cfg)):
        mixer, span, leaves = cache_leaves(cfg, kind)
        (got_mixer, pool), = tree[f"layers_{i}"].items()
        assert got_mixer == mixer and set(leaves) <= set(pool)
        for leaf, (shape, dtype) in leaves.items():
            held = pool[leaf]
            assert held.shape[0] == blocks[KINDS[kind].table] and held.dtype == dtype
            # an entry a token of a block, or one a block; padded, never cut
            entries = 1 if span == "row" else BS
            assert held[0].size >= entries * int(np.prod(shape))
            assert (span == "row") == (held.shape[1:] == shape)
    # the step takes this tree and gives back one of the same shapes
    cache = PagedKVCache(model, 3, num_blocks=16, block_size=BS, chunk_tokens=16)
    assert spec_of(cache.tree) == spec_of(init_paged_cache(
        model, 16, BS, window_blocks=cache.window_num_blocks or None,
        state_blocks=cache.state_num_blocks or None))
    step = paged_programs(model, 0.0, None)[3]
    lanes, rngs = jnp.zeros((3,), jnp.int32), jnp.zeros((3, 2), jnp.uint32)
    out = jax.eval_shape(step, variables["params"], cache.tree, lanes, lanes, rngs, cache.tables())
    assert spec_of(out[0]) == spec_of(cache.tree)


def test_a_family_with_riders_needs_its_blocks(case):
    name, model, _ = case
    kinds = set(layers_of(model.cfg))
    if "window" in kinds:
        with pytest.raises(ValueError, match="^a model with window layers needs window_blocks$"):
            init_paged_cache(model, 16, BS, state_blocks=3)
    if kinds & set(STATE_KINDS):
        with pytest.raises(ValueError, match="^a model with linear or conv layers needs state_blocks$"):
            init_paged_cache(model, 16, BS, window_blocks=6)
    if "latent" in kinds:
        with pytest.raises(ValueError, match="^a latent pool has no int8 form$"):
            init_paged_cache(model, 16, BS, quantized=True, window_blocks=6, state_blocks=3)
    if kinds == {"full"}:
        tree = init_paged_cache(model, 16, BS, quantized=True)
        assert {leaf: (a.shape, str(a.dtype)) for leaf, a in tree["layers_0"]["attn"].items()} == {
            "k": ((16, BS, 2, 8), "int8"), "v": ((16, BS, 2, 8), "int8"),
            "k_scale": ((16, BS, 2), "float32"), "v_scale": ((16, BS, 2), "float32")}


# --- (b) each kind is handed its family's table --------------------------------

def test_tables_hands_each_kind_its_family_s_table(case):
    _, model, _ = case
    cache = PagedKVCache(model, 3, num_blocks=24, block_size=BS, chunk_tokens=16)
    a, b = cache.allocate(), cache.allocate()
    cache.ensure_blocks(a, 15, 0), cache.ensure_blocks(b, 7, 0), cache.ensure_blocks(a, 31, 16)
    family = {
        "blocks": (cache.block_tables, cache.invalid_block),
        "window": (cache.window_tables, cache.window_invalid_block),
        "state": (cache.state_table, cache.state_invalid_block),
    }
    assert cache.kinds == model.cfg.cache_kinds == tuple(kind.name for kind in cache.records)
    got = cache.tables()
    assert isinstance(got, tuple) == (len(cache.kinds) > 1)
    got = got if isinstance(got, tuple) else (got,)
    parked = cache.tables(parked=[b])
    parked = parked if isinstance(parked, tuple) else (parked,)
    row = cache.tables(slice(a, a + 1))
    row = row if isinstance(row, tuple) else (row,)
    assert len(got) == len(cache.kinds)
    for kind, table, with_parked, one in zip(cache.records, got, parked, row):
        own, invalid = family[kind.table]
        assert np.array_equal(table, own) and not np.shares_memory(table, own)
        assert (table[a] != invalid).any() and (table[2] == invalid).all()
        assert (with_parked[b] == invalid).all() and np.array_equal(with_parked[a], own[a])
        assert np.array_equal(one, own[a:a + 1])
        table[:] = -7  # a copy: the cache's own stays
        assert (own != -7).all()
    # the layers counted, a kind at a time and a family at a time
    count = {name: layers_of(model.cfg).count(name) for name in CACHE_KINDS}
    assert cache.layers == {name: count[name] for name in cache.kinds}
    assert (cache.full_layers, cache.window_layers, cache.linear_layers,
            cache.latent_layers, cache.conv_layers) == tuple(count[k] for k in CACHE_KINDS)
    assert cache.state_layers == count["linear"] + count["conv"]
    # what the gauges of `/serve` are made of adds up to everything pinned
    gauges = cache.pool_gauges()
    assert set(gauges) == {kind.gauge for kind in cache.records}
    assert sum(live * nbytes for live, nbytes, _ in gauges.values()) == cache.bytes_live == (
        cache.live_blocks * cache.bytes_per_block
        + cache.window_live_blocks * cache.window_bytes_per_block
        + cache.state_live_blocks * cache.state_bytes_per_block) > 0
    assert cache.scale_bytes_per_block == 0
    cache.free(a), cache.free(b)
    assert cache.bytes_live == 0


def test_bytes_a_block_are_the_pool_s_own_leaves(case):
    """The parent's formulas, written out: K and V of the held shape a full or
    window layer, the held row a latent layer, the mixer's leaves a state layer."""
    _, model, _ = case
    cfg = model.cfg
    cache = PagedKVCache(model, 3, num_blocks=24, block_size=BS, chunk_tokens=16)
    item = 4  # float32
    kv = 2 * BS * cfg.kv_heads * cfg.head_dim * item
    state = {
        "linear": (2 * 8 * 12) * 4 + (cfg.linear_conv - 1) * 2 * (2 * 8 + 12) * item,
        "conv": (cfg.conv_taps - 1) * cfg.d_model * item,
    }
    assert cache.bytes_per_block == (
        cache.full_layers * kv + cache.latent_layers * BS * cfg.latent_width * item)
    assert cache.window_bytes_per_block == cache.window_layers * kv
    assert cache.state_bytes_per_block == (
        cache.linear_layers * state["linear"] + cache.conv_layers * state["conv"])
    assert cache.dense_bytes_per_request == (
        (cache.full_layers + cache.window_layers) * 2 * M * cfg.kv_heads * cfg.head_dim * item
        + cache.latent_layers * M * cfg.latent_width * item + cache.state_bytes_per_block)
    quantized = set(cache.kinds) == {"full"}
    if quantized:
        q = PagedKVCache(model, 3, num_blocks=24, block_size=BS, quantized=True)
        assert q.scale_bytes_per_block == 2 * cfg.n_layers * BS * cfg.kv_heads * 4
        assert q.bytes_per_block == kv // item * cfg.n_layers + q.scale_bytes_per_block
        assert q.wire_dtype == "int8" and q.avals["full"]["k"].dtype == jnp.int8


# --- (c) what is not carried is refused in the parent's words -------------------

STATE_WORDS = {
    "prefix_cache": "prefix_cache=True (a shared prefix's recurrent state is not "
                    "snapshotted at the prefix's end)",
    "kv_quant": "kv_quant=True (an int8 pool beside float32 state blocks is untested)",
    "mesh": "mesh= (the state pool and the recurrence are not partitioned over tp)",
    "role": "role='prefill' (block migration moves K/V blocks, not a state block)",
    "precompiled": "precompiled= (pre-warmed programs take one table)",
}
PARENT_S_WORDS = {
    "window": {
        "prefix_cache": "prefix_cache=True (a shared prefix's window-layer blocks are "
                        "recycled under its other holders)",
        "kv_quant": "kv_quant=True (an int8 pool of two kinds of blocks is untested)",
        "mesh": "mesh= (the window pool and the windowed decode kernel are not "
                "partitioned over tp)",
        "role": "role='prefill' (block migration moves one kind of block)",
        "precompiled": "precompiled= (pre-warmed programs take one table)",
    },
    "linear": STATE_WORDS,
    "latent": {
        "kv_quant": "kv_quant=True (a latent pool has no int8 form)",
        "mesh": "mesh= (a latent pool has no KV heads to partition over tp)",
        "role": "role='prefill' (block migration moves K/V blocks, not latent ones)",
        "precompiled": "precompiled= (pre-warmed programs take a K/V pool)",
    },
    "conv": STATE_WORDS,
}
FEATURES = ("prefix_cache", "kv_quant", "mesh", "role", "precompiled")


def asking(feature):
    if feature == "mesh":
        from pytorch_distributed_example_tpu.mesh import init_device_mesh

        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        return {"mesh": init_device_mesh(("tp",), (2,), devices=jax.devices()[:2])}
    return {
        "prefix_cache": {"prefix_cache": True}, "kv_quant": {"kv_quant": True},
        "role": {"role": "prefill"},
        # executables of another engine's shapes: none is attached
        "precompiled": {"precompiled": {("step", 99): object()}},
    }[feature]


@pytest.fixture(scope="module")
def models():
    built = {}
    for name in CASES:
        model = model_of(name)
        built[name] = (model, model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    return built


@pytest.mark.parametrize("feature", FEATURES)
@pytest.mark.parametrize("kind", CACHE_KINDS)
def test_a_feature_is_refused_in_the_parent_s_words_or_served(models, kind, feature):
    assert set(KINDS[kind].not_carried) == set(PARENT_S_WORDS.get(kind, ()))
    assert set(KINDS[kind].not_carried) <= set(FEATURES)
    model, variables = models[kind]
    build = lambda: ServeEngine(
        model, variables, slots=2, block_size=BS, min_bucket=8, **asking(feature))
    if feature in KINDS[kind].not_carried:
        with pytest.raises(ValueError) as said:
            build()
        assert str(said.value) == (
            f"a model with {kind} layers cannot be served with {PARENT_S_WORDS[kind][feature]}")
    else:
        engine = build()
        assert kind in engine.cache.kinds


def test_the_first_kind_that_refuses_is_the_one_named(models):
    """Of a model's kinds the first in `CACHE_KINDS`' order, and of what was
    asked the first in its record's."""
    model, variables = models["all_five"]
    with pytest.raises(ValueError, match="^a model with window layers cannot be served with "
                                         "kv_quant=True \\(an int8 pool of two kinds"):
        ServeEngine(model, variables, slots=2, block_size=BS, min_bucket=8, role="decode",
                    kv_quant=True)
    with pytest.raises(ValueError, match="^a model with window layers cannot be served with "
                                         "role='decode' \\(block migration moves one kind"):
        ServeEngine(model, variables, slots=2, block_size=BS, min_bucket=8, role="decode")


# --- (d) the paths and the shared pass, as the parent named them ----------------

def shape_of(name):
    """The models `tests/test_scope_names.py` builds: narrow ones, whose every
    layer gathers, and ones wide enough that a kernel takes the call."""
    full = RopeSpec(5e5, 0.5, (8.0, 16, 64.0, 1.0, 1.4))
    common = dict(vocab_size=64, d_ff=64, max_seq_len=M, use_flash=False)
    pattern = dict(
        d_model=32, n_heads=4, n_kv_heads=2, n_layers=3, window=8, attn_gate=True,
        rope_pairs="halves", sparse_experts=4, sparse_top_k=2, sparse_d_ff=16,
        shared_d_ff=16, routed_scale=2.5,
        layers=(LayerSpec("full", 4, full, "dense"), LayerSpec("window", 8, RopeSpec(1e4), "sparse"),
                LayerSpec("full", 4, full, "sparse")))
    hybrid = dict(
        d_model=32, n_heads=2, n_layers=4, post_norm=True, qk_norm=True, linear_heads=2,
        linear_neg_eigval=True,
        layers=(LayerSpec("linear"),) * 3 + (LayerSpec("full", rope=RopeSpec(rotary_fraction=0.0)),))
    latent = dict(
        d_model=32, n_heads=2, n_layers=2, sandwich_norm=True, latent_q_rank=8,
        latent_nope_dim=8, latent_v_dim=8, layers=(LayerSpec("latent"),) * 2)
    rope = RopeSpec(1e6)
    return TransformerLM(TransformerConfig(**common, **{
        "plain": dict(d_model=32, n_heads=4, n_kv_heads=2, n_layers=2),
        "plain_wide": dict(d_model=512, n_heads=4, n_kv_heads=2, n_layers=2),
        "pattern16": dict(pattern, head_size=16),
        "pattern128": dict(pattern, head_size=128),
        "hybrid": dict(hybrid, linear_key_dim=8, linear_value_dim=12),
        "hybrid_wide": dict(hybrid, linear_key_dim=16, linear_value_dim=64),
        "latent16": dict(latent, latent_kv_rank=16, latent_rope_dim=4),
        "latent128": dict(latent, latent_kv_rank=128, latent_rope_dim=128),
        "conv": dict(
            d_model=256, n_heads=4, n_kv_heads=2, n_layers=3, rope_pairs="halves",
            qk_head_norm=True, tie_embeddings=True,
            layers=(LayerSpec("conv", rope=rope), LayerSpec("conv", rope=rope),
                    LayerSpec("full", rope=rope))),
    }[name]))


# shape -> (the step's paths, a 16-token chunk's, a 4-token chunk's, whether the
# step reads a shared block once): the parent's answers
PARENT_S_PATHS = {
    "plain": ({"full": (2, "gather")},) * 3 + (False,),
    "plain_wide": ({"full": (2, "decode_kernel")}, {"full": (2, "chunk_kernel")},
                   {"full": (2, "gather")}, True),
    "pattern16": ({"full": (2, "gather"), "window": (1, "gather")},) * 3 + (False,),
    "pattern128": ({"full": (2, "decode_kernel"), "window": (1, "decode_kernel")},
                   {"full": (2, "chunk_kernel"), "window": (1, "gather")},
                   {"full": (2, "gather"), "window": (1, "gather")}, True),
    "hybrid": ({"full": (1, "gather"), "linear": (3, "recurrence")},
               {"full": (1, "gather"), "linear": (3, "chunk_scan")},
               {"full": (1, "gather"), "linear": (3, "chunk_scan")}, False),
    "hybrid_wide": ({"full": (1, "gather"), "linear": (3, "recurrence_kernel")},
                    {"full": (1, "gather"), "linear": (3, "chunk_scan")},
                    {"full": (1, "gather"), "linear": (3, "chunk_scan")}, False),
    "latent16": ({"latent": (2, "gather")},) * 3 + (False,),
    "latent128": ({"latent": (2, "latent_decode_kernel")}, {"latent": (2, "latent_chunk_kernel")},
                  {"latent": (2, "gather")}, True),
    "conv": ({"conv": (2, "conv_step"), "full": (1, "decode_kernel")},
             {"conv": (2, "conv_chunk"), "full": (1, "chunk_kernel")},
             {"conv": (2, "conv_chunk"), "full": (1, "gather")}, True),
}


@pytest.mark.parametrize("shape", sorted(PARENT_S_PATHS))
def test_layer_paths_and_the_shared_pass_answer_as_the_parent_s(shape):
    cache = PagedKVCache(shape_of(shape), 4, num_blocks=24, block_size=BS, chunk_tokens=16)
    step, chunk16, chunk4, shares = PARENT_S_PATHS[shape]
    assert layer_paths(cache, 4, 1) == step
    assert layer_paths(cache, 1, 16) == chunk16 and layer_paths(cache, 1, 4) == chunk4
    assert step_shares_blocks(cache, step) is shares
    # a chunk's paths name no decode kernel: nothing is shared there
    assert step_shares_blocks(cache, chunk16) is False


def test_the_snapshot_counts_the_pool_a_kind_at_a_time(case):
    """`/serve`'s `cache_pool`: the parent's keys, and at every step the values
    the cache holds."""
    name, model, variables = case
    engine = ServeEngine(model, variables, slots=3, block_size=BS, pool_blocks=40,
                         prefill_chunk_tokens=16, min_bucket=8)
    rng = np.random.RandomState(3)
    for i, (n, new) in enumerate(((45, 6), (7, 3), (13, 4))):
        engine.submit(rng.randint(1, 60, size=n).astype(np.int32), new, rid=f"r{i}")
    steps, seen = 0, set()
    cache = engine.cache
    while engine.step():
        steps += 1
        assert steps < 200
        pool = engine.metrics.snapshot()["cache_pool"]
        # the gauges are the pool as the call found it, before its own work
        gauges = engine.metrics.pool_gauges
        live = lambda gauge: gauges.get(gauge, (0, 0, 0))[0]
        nbytes = lambda gauge: gauges.get(gauge, (0, 0, 0))[1]
        assert pool["full_blocks_live"] == pool["blocks_live"]
        assert pool["window_blocks_live"] == live("window")
        assert pool["window_blocks_recycled"] == gauges.get("window", (0, 0, 0))[2]
        assert pool["state_blocks_live"] == live("state")
        assert pool["state_bytes_live"] == live("state") * cache.state_bytes_per_block
        assert pool["latent_blocks_live"] == live("latent") == (
            pool["blocks_live"] if cache.latent_layers else 0)
        assert pool["latent_bytes_live"] == live("latent") * nbytes("latent")
        assert pool["bytes_live"] == (
            pool["blocks_live"] * cache.bytes_per_block
            + pool["window_blocks_live"] * cache.window_bytes_per_block
            + pool["state_bytes_live"])
        seen |= {gauge for gauge in gauges if live(gauge)}
    assert seen == {kind.gauge for kind in cache.records}
    assert len(engine.completions) == 3
    snap = engine.metrics.snapshot()
    assert sorted(snap["cache_pool"]) == [
        "blocks_live", "blocks_total", "bytes_live", "bytes_per_live_request_mean",
        "dense_bytes_per_request", "dense_reduction_x", "effective_slots", "full_blocks_live",
        "latent_blocks_live", "latent_bytes_live", "mean_utilization", "scale_overhead_bytes",
        "state_blocks_live", "state_bytes_live", "utilization", "window_blocks_live",
        "window_blocks_recycled", "wire_dtype"]
    want = {kind.name: [cache.layers[kind.name], path] for kind, path in zip(
        cache.records, (layer_paths(cache, 3, 1)[k][1] for k in cache.kinds))}
    assert snap["decode"]["layer_paths"] == want
    assert set(snap["prefill"]["layer_paths"]) == set(cache.kinds)
    assert engine._pad_id == (-1 if set(cache.kinds) & set(STATE_KINDS) else 0)


# --- (e) no module of the serve plane but the records' names a kind -------------

def kind_literals(path):
    """The string constants of a module that are a kind's name, docstrings
    aside: (line, value)."""
    tree = ast.parse(path.read_text())
    docstrings = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef)):
            body = node.body
            if body and isinstance(body[0], ast.Expr) and isinstance(body[0].value, ast.Constant):
                docstrings.add(id(body[0].value))
    return [
        (node.lineno, node.value) for node in ast.walk(tree)
        if isinstance(node, ast.Constant) and node.value in CACHE_KINDS
        and id(node) not in docstrings
    ]


@pytest.mark.parametrize("module", ["decode.py", "engine.py", "cache.py"])
def test_no_kind_is_written_out_by_name(module):
    """A kind is data: `serve/decode.py` and `serve/engine.py` ask the records
    (the parent named kinds 7 and 2 times there). `serve/cache.py` names its
    three FAMILIES of table, of which "window" is also a kind's name."""
    found = kind_literals(SERVE / module)
    if module == "cache.py":
        found = [(line, value) for line, value in found if value != "window"]
    assert found == []


def test_the_walk_finds_a_kind_written_out(tmp_path):
    src = tmp_path / "m.py"
    src.write_text('"""full"""\ndef f(kind):\n    """latent"""\n    return kind == "conv" or "convolve"\n')
    assert kind_literals(src) == [(4, "conv")]
    assert kind_literals(SERVE / "kinds.py")  # the one place
