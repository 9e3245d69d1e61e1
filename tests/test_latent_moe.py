"""A model with latent attention on the serve path, at a small size on the
CPU: multi-head latent attention over a latent paged cache (one row a token
in one pool a layer), sandwich norms, leading dense layers and a
sigmoid-routed dropless MoE with a shared expert of which a chip holds a
share. The program's model is built by `bench_matrix/glue/latent_moe.py`
from a configuration in the published file's own keys, and compared with
`bench_matrix/reference/latent_moe.py` (the NON-absorbed equations) on
seeded weights in float32: the test of the layer's equations and of the
absorbed cached paths."""

import copy
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_matrix import correctness, modelglue
from bench_matrix.glue import latent_moe as glue
from bench_matrix.reference import latent_moe as reference
from pytorch_distributed_example_tpu.models.generate import generate, init_cache
from pytorch_distributed_example_tpu.models.transformer import (
    LayerSpec, TransformerConfig, TransformerLM,
)
from pytorch_distributed_example_tpu.ops import (
    gather_paged_latent, latent_chunk_attention, latent_decode_attention, paged_kernel,
    pool_latent_width,
)
from pytorch_distributed_example_tpu.parallel.expert_parallel import dropless_moe
from pytorch_distributed_example_tpu.serve import ServeEngine
from pytorch_distributed_example_tpu.serve.bucketing import bucket_for
from pytorch_distributed_example_tpu.serve.cache import PagedKVCache, init_paged_cache

from test_sparse_window import Probe  # keeps every prefill chunk's (start, tokens, logits)

ROOT = Path(__file__).resolve().parents[1]
PUBLISHED = json.loads(
    (ROOT / "bench_matrix" / "configs" / "openpangu-ultra-moe-718b-d7.json").read_text())
BS, M = 8, 160
F32 = {"weights": "float32", "activations": "float32", "kv_cache": "float32"}


def toy(**sizes):
    """The published file cut to a toy: every mechanism, no width of the
    model's; `published` follows where the glue reads it (the router's
    width)."""
    cfg = dict(PUBLISHED, **sizes, dtype=F32)
    cfg["published"] = dict(PUBLISHED["published"], n_routed_experts=sizes["router_width"])
    cfg.pop("router_width")
    cfg["model"] = {k: v for k, v in PUBLISHED["model"].items() if k != "check"}
    return cfg


# one dense layer, then two sparse ones; rows of 40 values: no kernel takes them
SMALL = toy(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, vocab_size=128,
    num_hidden_layers=3, first_k_dense_replace=1, n_routed_experts=8, router_width=8,
    num_experts_per_tok=2,
)
# the same with a latent of 128 values (rows of 144, held as 256): both
# kernels take the pool, interpreted here
WIDE = dict(SMALL, kv_lora_rank=128, qk_rope_head_dim=16)
LIMITS = {"max_rel": 1e-4, "rms_rel": 1e-4}


def build(config, seed=7):
    model = modelglue.build_model(config, M, remat=False)
    return model, modelglue.make_variables(model, config, seed=seed)


@pytest.fixture(scope="module")
def small():
    return build(SMALL)


@pytest.fixture(scope="module")
def wide():
    return build(WIDE)


def latent_gauge(cache):
    """(live blocks, bytes one of them pins) that `cache` counts for its latent
    layers (`PagedKVCache.pool_gauges`); (0, 0) for a model with none."""
    return cache.pool_gauges().get("latent", (0, 0, 0))[:2]


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, SMALL["vocab_size"], (n,), dtype=np.int32)


def reference_logits(variables, tokens, last, config=SMALL, **kw):
    emb, layers, norm, w_out = glue.reference_parts(variables)
    return np.asarray(reference.logits(tokens, emb, layers, norm, w_out, config,
                                       last=last, **kw))


# --- (i) the equations -------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_cache_free_forward_is_the_reference(small, seed):
    model, variables = small
    tokens = tokens_of(40, seed)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    out = correctness.compare(got, reference_logits(variables, tokens, 40), LIMITS)
    assert out["ok"], out


def test_the_pattern_is_what_the_configuration_says(small):
    model, variables = small
    cfg = model.cfg
    assert [s.attention for s in cfg.layers] == ["latent"] * 3
    assert [s.mlp for s in cfg.layers] == ["dense", "sparse", "sparse"]
    assert cfg.cache_kinds == ("latent",) and cfg.latent_layers == (0, 1, 2)
    assert (cfg.sandwich_norm, cfg.sparse_score, cfg.routed_scale) == (True, "sigmoid", 2.5)
    assert cfg.latent_width == 40 and cfg.experts_held is None
    blk = variables["params"]["layers_1"]
    assert set(blk) == {"attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm",
                        "latent_attn", "mlp"}
    assert set(blk["latent_attn"]) == {"q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj",
                                       "kv_a_norm", "kv_b_proj", "o_proj"}
    assert blk["latent_attn"]["kv_b_proj"].shape == (32, 4 * (16 + 16))
    assert blk["latent_attn"]["kv_a_proj"]["kernel"].shape == (64, 32 + 8)
    assert blk["mlp"]["router"].shape == (64, 8)
    # the published model: 8 of 256 held, the router 256 wide
    full = modelglue.build_model(PUBLISHED, 16384, remat=False).cfg
    assert (full.sparse_experts, full.experts_held) == (256, (0, 8))
    assert (full.latent_q_rank, full.latent_kv_rank, full.latent_nope_dim,
            full.latent_rope_dim, full.latent_v_dim) == (1536, 512, 128, 64, 128)
    assert full.latent_width == 576 and full.sparse_layers == (3, 4, 5, 6)
    assert full.layers[0].rope.theta == 25.6e6 and full.rope_pairs == "interleaved"


def _mutated(variables, path, fn):
    out = copy.deepcopy(jax.tree_util.tree_map(np.asarray, variables))
    node = out["params"]
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = fn(node[path[-1]])
    return out


MECHANISMS = {
    # a parameter only this mechanism reads, changed: the logits must move
    "the_norm_on_the_latent": ("layers_0", "latent_attn", "kv_a_norm", "scale"),
    "the_norm_on_the_low_rank_query": ("layers_1", "latent_attn", "q_a_norm", "scale"),
    "the_norm_on_the_attention_output": ("layers_0", "attn_post_norm", "scale"),
    "the_norm_on_the_mlp_output": ("layers_2", "mlp_post_norm", "scale"),
    "the_shared_expert": ("layers_1", "mlp", "shared_expert", "down_proj", "kernel"),
    "the_router": ("layers_2", "mlp", "router"),
}


@pytest.mark.parametrize("what", sorted(MECHANISMS))
def test_the_comparison_sees_each_mechanism(small, what):
    """Model and reference read the same parameter the same way: changed on
    both sides they still agree, changed on one they do not."""
    model, variables = small
    tokens = tokens_of(24, 3)
    rng = np.random.default_rng(1)
    changed = _mutated(variables, MECHANISMS[what],
                       lambda a: a * rng.uniform(0.5, 1.5, a.shape).astype(a.dtype))
    got = model.apply(changed, jnp.asarray(tokens)[None])[0]
    assert correctness.compare(got, reference_logits(changed, tokens, 24), LIMITS)["ok"]
    assert not correctness.compare(got, reference_logits(variables, tokens, 24), LIMITS)["ok"]


@pytest.mark.parametrize("fault", ["k_rope_unrotated", "c_kv_unnormed"])
def test_a_key_cached_before_its_rotation_or_a_latent_before_its_norm_fails(small, fault):
    """What a cache that took its row a step too early would hold, planted in
    the reference: the comparison with the sound model fails."""
    model, variables = small
    tokens = tokens_of(40, 5)
    # a latent norm of scale 1 on unit-variance weights changes little: give it work
    scaled = _mutated(variables, ("layers_0", "latent_attn", "kv_a_norm", "scale"),
                      lambda a: a * 1.7)
    got = model.apply(scaled, jnp.asarray(tokens)[None])[0]
    assert correctness.compare(got, reference_logits(scaled, tokens, 40), LIMITS)["ok"]
    out = correctness.compare(got, reference_logits(scaled, tokens, 40, fault=fault),
                              {"max_rel": 1e-2, "rms_rel": 1e-2})
    assert not out["ok"], out


def test_a_latent_cache_in_float8_fails_the_comparison(small):
    model, variables = small
    tokens = tokens_of(40, 5)
    want = reference_logits(variables, tokens, 40)
    for kw in ({"kv_dtype": jnp.float8_e4m3fn}, {"expert_dtype": jnp.float8_e4m3fn}):
        low = reference_logits(variables, tokens, 40, **kw)
        assert not correctness.compare(low, want, {"max_rel": 1e-3, "rms_rel": 1e-3})["ok"], kw


# --- (ii) absorbed against non-absorbed --------------------------------------

@pytest.mark.parametrize("which", ["small", "wide"])
def test_the_absorbed_cached_mixer_is_the_mixer_as_written(which, request):
    """`decode=True` over `generate()`'s cache runs every head on the one
    cached row (absorbed); `decode=False` up-projects keys and values: the
    same logits from the same weights, prefill and then one token at a
    time."""
    model, variables = request.getfixturevalue(which)
    tokens = tokens_of(30, 9)
    want = jax.jit(model.apply)(variables, jnp.asarray(tokens)[None])[0]
    cached = jax.jit(lambda cache, toks: model.apply(
        {"params": variables["params"], "cache": cache}, toks, decode=True,
        mutable=["cache"]))
    got, out = cached(init_cache(model, 1), jnp.asarray(tokens[:21])[None])
    rows = [got[0]]
    for t in tokens[21:]:
        step, out = cached(out["cache"], jnp.asarray([[t]]))
        rows.append(step[0])
    assert correctness.compare(np.concatenate(rows), want, LIMITS)["ok"]
    assert int(out["cache"]["layers_0"]["latent_attn"]["index"]) == 30


def test_the_generate_cache_is_the_module_s_own(small):
    model, _ = small
    made = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32), decode=True))["cache"]
    same = jax.tree_util.tree_map(lambda a, b: (a.shape, a.dtype) == (b.shape, b.dtype),
                                  init_cache(model, 2), dict(made))
    assert all(jax.tree_util.tree_leaves(same))
    assert made["layers_0"]["latent_attn"]["latent"].shape == (2, M, 40)


# --- (iii) the cached paths against the reference's logits -------------------

def _serve_by_hand(model, variables, prompts, steps, chunk=16, buckets=(8, 16), parked=(1,)):
    """Prefill then decode through `PagedKVCache` and the model's paged call,
    as `serve/decode.py`'s programs make it, keeping LOGITS: every prompt in
    chunks whose last is cut to its bucket and padded with -1, then `steps`
    decode steps of all slots at once with the `parked` slots' table rows
    all-invalid. Returns {row: (tokens, (len(tokens) - len(prompt) + 1 ...)
    logits rows from the prompt's last position on)}."""
    slots = len(prompts) + len(parked)
    cache = PagedKVCache(model, slots, block_size=BS, chunk_tokens=chunk)
    rows = [s for s in range(slots) if s not in parked]
    for _ in range(slots):
        cache.allocate()

    @jax.jit
    def program(params, tree, tokens, positions, tables):
        logits, out = model.apply(
            {"params": params, "cache": tree}, jnp.maximum(tokens, 0), decode=True,
            positions=positions, block_tables=tables,
            mutable=["cache", "intermediates"], row_mask=tokens >= 0)
        return out["cache"], logits

    def apply(tokens, positions, tables):
        cache.tree, logits = program(
            variables["params"], cache.tree, jnp.asarray(tokens),
            jnp.asarray(positions, jnp.int32), jnp.asarray(tables))
        return np.asarray(logits)

    seqs, kept = {}, {}
    for s, prompt in zip(rows, prompts):
        start = 0
        while start < len(prompt):
            size = min(bucket_for(min(len(prompt) - start, chunk), buckets), chunk)
            end = min(start + size, len(prompt))
            piece = np.full((1, size), -1, np.int32)
            piece[0, :end - start] = prompt[start:end]
            assert cache.ensure_blocks(s, end - 1, start)
            lg = apply(piece, [start], cache.tables(slice(s, s + 1)))
            at, start = len(prompt) - 1 - start, end  # the prompt's end, in a last chunk
        assert at < size - 1  # the last chunk was padded
        cache.lengths[s] = len(prompt)
        kept[s] = [lg[0, at]]
        seqs[s] = list(prompt) + [int(np.argmax(lg[0, at]))]
    for _ in range(steps):
        for s in rows:
            assert cache.ensure_blocks(s, int(cache.lengths[s]), int(cache.lengths[s]))
        last = np.zeros((slots, 1), np.int32)
        pos = np.full((slots,), M - 1, np.int32)  # a parked lane's clamp
        for s in rows:
            last[s, 0], pos[s] = seqs[s][-1], cache.lengths[s]
        lg = apply(last, pos, cache.tables(parked=list(parked)))
        for s in rows:
            kept[s].append(lg[s, 0])
            seqs[s].append(int(np.argmax(lg[s, 0])))
            cache.lengths[s] += 1
    return {s: (np.asarray(seqs[s], np.int32), np.stack(kept[s])) for s in rows}, cache


@pytest.mark.parametrize("which", ["small", "wide"])
def test_prefill_then_decode_through_the_latent_cache_gives_the_reference_s_logits(
        which, request):
    """Three live rows and a parked lane between them; prompts of 21, 37 and
    9 tokens end inside a bucket (chunks of 16, buckets 8 and 16), so every
    last chunk is padded; then 6 decoded positions, compared as LOGITS with
    the reference's full forward over prompt + tokens so far. `wide` runs
    both Pallas kernels (interpreted), `small` the gather + einsum."""
    model, variables = request.getfixturevalue(which)
    config = WIDE if which == "wide" else SMALL
    rank = config["kv_lora_rank"]
    pool = jax.ShapeDtypeStruct((64, BS, pool_latent_width(rank + config["qk_rope_head_dim"])),
                                jnp.float32)
    tables = jax.ShapeDtypeStruct((4, M // BS), jnp.int32)
    want_path = ("latent_decode", "latent_chunk") if which == "wide" else (None, None)
    assert (paged_kernel(1, pool, tables, rank=rank),
            paged_kernel(16, pool, tables, rank=rank)) == want_path
    prompts = [tokens_of(21, 1), tokens_of(37, 2), tokens_of(9, 3)]
    served, cache = _serve_by_hand(model, variables, prompts, steps=6)
    assert sorted(served) == [0, 2, 3]
    for s, prompt in zip(sorted(served), prompts):
        seq, logits = served[s]
        assert len(seq) == len(prompt) + 7 and logits.shape[0] == 7
        want = reference_logits(variables, seq[:-1], 7, config)
        out = correctness.compare(logits, want, LIMITS)
        assert out["ok"], (s, out)
    # the parked lane wrote nothing and holds nothing
    assert cache.slot_blocks(1) == [] and (cache.block_tables[1] == cache.invalid_block).all()


def test_a_neighbour_s_block_in_the_table_fails_the_comparison(small, monkeypatch):
    """A table entry that names another row's block: the decoded logits
    leave the reference's."""
    model, variables = small
    prompts = [tokens_of(21, 1), tokens_of(37, 2)]
    sound, _ = _serve_by_hand(model, variables, prompts, steps=2, parked=())
    seq, logits = sound[0]
    want = reference_logits(variables, seq[:-1], 3)
    assert correctness.compare(logits, want, LIMITS)["ok"]

    class Swapped(PagedKVCache):
        def tables(self, rows=slice(None), parked=()):
            t = super().tables(rows, parked)
            if t.shape[0] == self.slots and self.lengths[0]:  # a decode step's
                t[0, 1] = self.block_tables[1, 1]
            return t

    monkeypatch.setitem(globals(), "PagedKVCache", Swapped)
    faulty, _ = _serve_by_hand(model, variables, prompts, steps=2, parked=())
    out = correctness.compare(faulty[0][1][1:], want[1:], {"max_rel": 1e-2, "rms_rel": 1e-2})
    assert not out["ok"], out


# --- (iv) through the engine --------------------------------------------------

@pytest.fixture(scope="module")
def served(small):
    """One engine run: a 45-token prompt in chunks of at most 16 (its last
    ends inside a bucket) decoded for 12 tokens, with shorter requests coming
    and going beside it on three slots."""
    model, variables = small
    engine = ServeEngine(model, variables, slots=3, block_size=BS, pool_blocks=40,
                         prefill_chunk_tokens=16, min_bucket=8)
    probe = engine._prefill_chunk = Probe(engine._prefill_chunk)
    prompt = tokens_of(45, 21)
    engine.submit(prompt, 12, rid="long")
    for i in range(4):
        engine.submit(tokens_of(7 + 6 * i, 30 + i), 3 + i, rid=f"short{i}")
    live, steps, shares = [], 0, []
    while engine.step():
        steps += 1
        blocks, nbytes, _ = engine.metrics.pool_gauges["latent"]
        live.append((blocks, blocks * nbytes, latent_gauge(engine.cache)[0]))
        shares.append((engine.metrics.moe_assignments, engine.metrics.moe_routed))
        assert steps < 400
    return {"engine": engine, "done": engine.completions, "prompt": prompt,
            "chunks": probe.chunks, "variables": variables, "live": live, "shares": shares}


def test_chunked_prefill_through_the_engine_gives_the_reference_s_logits(served):
    prompt = served["prompt"]
    mine = [(s, t, lg) for s, t, lg in served["chunks"]
            if len(t) and np.array_equal(t, prompt[s:s + len(t)])]
    assert sum(len(t) for _, t, _ in mine) == 45 and len(mine) >= 3
    assert any(len(t) < lg.shape[0] for _, t, lg in mine)  # a padded last chunk
    want = reference_logits(served["variables"], prompt, 45)
    for start, t, lg in mine:
        out = correctness.compare(lg[:len(t)], want[start:start + len(t)], LIMITS)
        assert out["ok"], (start, out)


@pytest.mark.parametrize("rid", ["long", "short0", "short1", "short2", "short3"])
def test_decoded_tokens_are_the_reference_s_choice(served, rid):
    done = served["done"][rid]
    prompt = served["prompt"] if rid == "long" else tokens_of(
        7 + 6 * int(rid[5:]), 30 + int(rid[5:]))
    full = np.concatenate([prompt, np.asarray(done.tokens[:-1], np.int32)])
    want = reference_logits(served["variables"], full, len(done.tokens))
    assert correctness.chosen_gap(want, done.tokens) <= 1e-4


def test_generate_gives_the_served_tokens(small, served):
    model, variables = small
    got = generate(model, variables, jnp.asarray(served["prompt"])[None], max_new_tokens=12)
    assert np.asarray(got)[0].tolist() == served["done"]["long"].tokens


def test_latent_blocks_are_counted_as_held_and_go_back_at_retirement(served):
    engine = served["engine"]
    cache = engine.cache
    assert cache.kinds == ("latent",) and cache.latent_layers == 3 and cache.full_layers == 0
    # a row of 40 values is held as it is; 3 layers x 8 tokens x 40 x 4 bytes
    assert cache.avals["latent"]["latent"].shape[-1] == 40 and latent_gauge(cache)[1] == 3 * BS * 40 * 4
    assert cache.bytes_per_block == latent_gauge(cache)[1]
    assert max(held for _, _, held in served["live"]) >= 6  # 45 tokens alone hold 6 blocks
    # the gauge is the pool as the step found it
    assert all(nbytes == b * latent_gauge(cache)[1] for b, nbytes, _ in served["live"])
    assert max(b for b, _, _ in served["live"]) >= 6
    assert cache.live_blocks == 0 and latent_gauge(cache)[0] == 0 and cache.bytes_live == 0
    snap = engine.metrics.snapshot()["cache_pool"]  # the last step's gauge
    assert (snap["latent_blocks_live"], snap["latent_bytes_live"]) == served["live"][-1][:2]
    paths = engine.metrics.snapshot()
    assert paths["decode"]["layer_paths"] == {"latent": [3, "gather"]}
    assert paths["prefill"]["layer_paths"] == {"latent": [3, "gather"]}


def test_every_routed_pair_is_computed_when_every_expert_is_held(served):
    """All 8 experts held: the assignments computed are the pairs routed,
    `top_k` a live row and sparse layer."""
    busy = [(a, r) for a, r in served["shares"] if r]
    assert busy and all(a == r and r % (2 * 2) == 0 for a, r in busy)
    moe = served["engine"].metrics.snapshot()["moe"]
    assert moe["routed_total"] == moe["assignments_total"] > 0


# --- (v) the kernels, interpreted, against gather + einsum -------------------

def _pool_and_tables(rng, lengths, nblk=64, nb=24, W=256, dtype=jnp.float32):
    pool = jnp.asarray(rng.normal(size=(nblk, BS * 2, W)), dtype)
    tables = np.full((len(lengths), nb), nblk, np.int32)
    free = list(rng.permutation(nblk))
    for b, n in enumerate(lengths):
        for j in range(n // (BS * 2) + 1 if n >= 0 else 0):
            tables[b, j] = free.pop()
    return pool, tables


def _einsum_reference(q, pool, tables, pos, rank, scale):
    """q (B, L, H, W) at absolute positions pos (B, L): the gather and the
    masked einsum the model falls back to."""
    from pytorch_distributed_example_tpu.models.transformer import (
        _latent_attention, _position_mask)

    held = gather_paged_latent(pool, jnp.asarray(tables))
    mask = _position_mask(jnp.asarray(pos), jnp.arange(held.shape[1])[None])
    return _latent_attention(q, held, rank, scale, mask)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("lengths", [(0, 15, 16), (255, 256, 257), (100, 31, 200)])
def test_the_latent_decode_kernel_is_the_masked_einsum(lengths, dtype, tol):
    """Lengths on both sides of a page edge (16) and of a key block's (256),
    a parked row (all-invalid table) beside them."""
    rng = np.random.default_rng(sum(lengths))
    pool, tables = _pool_and_tables(rng, lengths + (-1,), dtype=dtype)
    at = np.asarray(lengths + (tables.shape[1] * 16 - 1,), np.int32)
    q = jnp.asarray(rng.normal(size=(4, 8, 256)), dtype)
    got = latent_decode_attention(q, pool, jnp.asarray(tables), jnp.asarray(at), 0.1,
                                  rank=128, interpret=True)
    want = _einsum_reference(q[:, None], pool, tables, at[:, None], 128, 0.1)[:, 0]
    assert got.shape == (4, 8, 128) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got[:3], np.float32), np.asarray(want[:3], np.float32),
                               atol=tol)
    assert not np.asarray(got[3], np.float32).any()  # no page read, zeros


def _layer_reference(q_nope, q_rope, w_uk, w_uv, pool, tables, pos, scale):
    """The layer as it is written, over the gathered rows: a key's heads
    up-projected from its latent (`kv_up`), the one rotary key beside them,
    a softmax masked by absolute position. q_nope (B, L, H, dn) and q_rope
    (B, L, H, dr) at positions pos (B, L)."""
    from pytorch_distributed_example_tpu.models.transformer import _position_mask

    rank, dr = w_uk.shape[0], q_rope.shape[-1]
    held = gather_paged_latent(pool, jnp.asarray(tables))
    c_kv, k_rope = held[..., :rank], held[..., rank:rank + dr]
    k_nope = jnp.einsum("bmr,rhd->bmhd", c_kv, w_uk)
    v = jnp.einsum("bmr,rhd->bmhd", c_kv, w_uv)
    f32 = dict(preferred_element_type=jnp.float32)
    s = (jnp.einsum("blhd,bmhd->bhlm", q_nope, k_nope, **f32)
         + jnp.einsum("blhd,bmd->bhlm", q_rope, k_rope, **f32)) * scale
    mask = _position_mask(jnp.asarray(pos), jnp.arange(held.shape[1])[None])
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), axis=-1).astype(v.dtype)
    return jnp.einsum("bhlm,bmhd->blhd", p, v)


def _chunk_operands(rng, B, L, H, dtype, rank=128, dn=32, dr=16, dv=24):
    """A chunk's queries as the layer makes them and its two up-projections."""
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    return (normal(B, L, H, dn), normal(B, L, H, dr),
            normal(rank, H, dn) * rank ** -0.5, normal(rank, H, dv) * rank ** -0.5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("L,start,H", [
    (64, 0, 8), (64, 200, 8), (32, 250, 8), (16, 37, 8),
    (64, 480, 8),   # the chunk's own keys on both sides of a key block's edge
    (32, 0, 48),    # heads in two groups of 24
    (64, 560, 48),  # two groups, three key blocks
    (1024, 40, 8),  # two query blocks: the second starts past the first's keys
])
def test_the_latent_chunk_kernel_is_the_masked_einsum(L, start, H, dtype, tol):
    """Chunks that start at a page's edge and inside one, at the row's first
    token, that cross a key block's edge with their queries' own keys, of one
    head group and of two, of one query block and of two: the kernel
    (interpreted) against the cache-free
    arithmetic over the gathered rows, so it is held to the layer's
    definition and to no other form of it."""
    from pytorch_distributed_example_tpu.ops.paged_attention import (
        CHUNK_QUERY_BLOCK, KEYS_PER_BLOCK, _latent_chunk_heads)

    assert _latent_chunk_heads(8)[0] == 8 and _latent_chunk_heads(48)[0] == 24
    assert (KEYS_PER_BLOCK, CHUNK_QUERY_BLOCK) == (256, 512)
    rng = np.random.default_rng(L + start)
    pool, tables = _pool_and_tables(rng, (start + L - 1,), nblk=96, nb=72, dtype=dtype)
    q_nope, q_rope, w_uk, w_uv = _chunk_operands(rng, 1, L, H, dtype)
    pool = pool.at[..., 128 + 16:].set(0)  # a pool holds zeros behind a row's values
    got = latent_chunk_attention(q_nope, q_rope, w_uk, w_uv, pool, jnp.asarray(tables),
                                 jnp.asarray([start]), 0.1, interpret=True)
    pos = start + np.arange(L)[None]
    want = _layer_reference(q_nope, q_rope, w_uk, w_uv, pool, tables, pos, 0.1)
    assert got.shape == (1, L, H, 24) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol)


def test_padded_queries_past_a_row_s_pages_stay_finite():
    """A last chunk's padding sits past the row's valid pages: it attends
    what the row has, and a row with no page returns zeros."""
    rng = np.random.default_rng(0)
    pool, tables = _pool_and_tables(rng, (40, -1))
    pool = pool.at[..., 128 + 16:].set(0)
    q_nope, q_rope, w_uk, w_uv = _chunk_operands(rng, 2, 32, 8, jnp.float32)
    got = np.asarray(latent_chunk_attention(
        q_nope, q_rope, w_uk, w_uv, pool, jnp.asarray(tables), jnp.asarray([32, 0]), 0.1,
        interpret=True))
    assert np.isfinite(got).all() and not got[1].any()
    want = _layer_reference(q_nope[:1, :9], q_rope[:1, :9], w_uk, w_uv, pool, tables[:1],
                            32 + np.arange(9)[None], 0.1)
    np.testing.assert_allclose(got[0, :9], np.asarray(want)[0], atol=2e-5)


@pytest.mark.parametrize("shape,rank,dtype,L,want", [
    ((8192, 16, 640), 512, jnp.bfloat16, 1, "latent_decode"),   # the cell's step
    ((8192, 16, 640), 512, jnp.bfloat16, 512, "latent_chunk"),  # and its chunk
    ((8192, 16, 640), 512, jnp.bfloat16, 128, "latent_chunk"),
    ((8192, 16, 576), 512, jnp.bfloat16, 1, None),   # a row Mosaic cannot copy whole
    ((64, 8, 40), 32, jnp.float32, 1, None),         # the toy of these tests
    ((64, 8, 256), 96, jnp.float32, 1, None),        # values that end inside a lane tile
    ((64, 8, 256), 128, jnp.bfloat16, 1, None),      # pages of half a sublane tile
    ((64, 16, 256), 128, jnp.int8, 1, None),
    ((64, 16, 256), 128, jnp.bfloat16, 20, None),    # a chunk of no whole sublane tiles
    ((64, 16, 256), 128, jnp.bfloat16, 48, "latent_chunk"),  # whole tiles: one query block
    ((8192, 16, 640), 512, jnp.bfloat16, 2048, "latent_chunk"),  # four query blocks
    ((8192, 16, 640), 512, jnp.bfloat16, 768, None),  # past a query block, not whole ones
])
def test_which_latent_pools_take_a_kernel(shape, rank, dtype, L, want):
    pool = jax.ShapeDtypeStruct(shape, dtype)
    tables = jax.ShapeDtypeStruct((8 if L == 1 else 1, 1024), jnp.int32)
    assert paged_kernel(L, pool, tables, rank=rank) == want


def test_a_row_is_held_in_whole_lane_tiles_past_the_first():
    assert [pool_latent_width(w) for w in (40, 128, 144, 576, 640)] == [40, 128, 256, 640, 640]


# --- (vi) the share ties to the model ----------------------------------------

def _moe_operands(T=24, D=64, F=16, E=32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    return (
        jax.random.normal(ks[0], (T, D)), jax.random.normal(ks[1], (D, E)) * 0.3,
        jax.random.normal(ks[2], (E, D, F)) * 0.1, jax.random.normal(ks[3], (E, D, F)) * 0.1,
        jax.random.normal(ks[4], (E, F, D)) * 0.1,
        [jax.random.normal(k, s) * 0.1 for k, s in zip(ks[5:], ((D, F), (D, F), (F, D)))],
    )


def test_the_parts_of_the_32_held_ranges_add_up_to_the_uncut_layer():
    """32 chips, each with one of 32 experts of a small sparse layer under
    the sigmoid router: the parts they compute, with the shared expert
    (which every chip computes alike) counted once, add up to what the UNCUT
    reference gives for the whole layer; and the 32 assignment counts to the
    pairs routed."""
    x, router, wg, wu, wd, shared = _moe_operands()
    kw = dict(n_experts=32, top_k=4, scale=2.5, score="sigmoid")
    ranged = jax.jit(lambda first, *a: dropless_moe(*a, first_expert=first, **kw))
    parts, assigned = 0.0, []
    for first in range(32):
        sl = slice(first, first + 1)
        part, st, chosen = ranged(first, x, router, wg[sl], wu[sl], wd[sl])
        parts, assigned = parts + part, assigned + [int(st[0])]
    assert sum(assigned) == 24 * 4 and max(assigned) < 24 * 4
    w = {"router": router, "experts_gate": wg, "experts_up": wu, "experts_down": wd,
         "shared_gate": shared[0], "shared_up": shared[1], "shared_down": shared[2]}
    want, routed = reference.sparse_mlp(x, w, top_k=4, scale=2.5, first_expert=0)
    once = reference.swiglu(x, *shared)
    np.testing.assert_allclose(np.asarray(parts + once), np.asarray(want), atol=2e-5)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), 1),
                                  np.sort(np.asarray(routed["chosen"]), 1))
    # and one chip's part is what the reference gives for that share
    sl = slice(8, 16)
    held = dict(w, experts_gate=wg[sl], experts_up=wu[sl], experts_down=wd[sl])
    part, _, _ = dropless_moe(x, router, wg[sl], wu[sl], wd[sl], first_expert=8, **kw)
    mine, _ = reference.sparse_mlp(x, held, top_k=4, scale=2.5, first_expert=8)
    np.testing.assert_allclose(np.asarray(part + once), np.asarray(mine), atol=2e-5)


@pytest.mark.parametrize("router_bias,few_enough", [(0.0, True), (4.0, False)])
def test_a_share_computes_its_leading_assignments_and_never_drops_one(router_bias, few_enough):
    """512 tokens, 4 of 32 experts a token, experts 8-9 held: a uniform router
    places ~128 of the 2048 assignments here and only the leading 512 rows of
    the sorted order are gathered and multiplied; a router that leans on the
    held experts places more than 512, and every assignment is computed as a
    chip that holds all experts computes them. Either way the reference's
    share, to float32."""
    x, router, wg, wu, wd, shared = _moe_operands(T=512)
    router = router.at[:, 8:10].add(router_bias * jnp.abs(x).mean())
    x = jnp.abs(x)  # so that the bias leans every token the same way
    kw = dict(n_experts=32, top_k=4, scale=2.5, score="sigmoid")
    sl = slice(8, 10)
    part, stats, _ = jax.jit(lambda *a: dropless_moe(*a, first_expert=8, **kw))(
        x, router, wg[sl], wu[sl], wd[sl])
    assert (int(stats[0]) <= 512) == few_enough and 0 < int(stats[0]) <= 2 * 512
    w = {"router": router, "experts_gate": wg[sl], "experts_up": wu[sl],
         "experts_down": wd[sl], "shared_gate": shared[0], "shared_up": shared[1],
         "shared_down": shared[2]}
    want, _ = reference.sparse_mlp(x, w, top_k=4, scale=2.5, first_expert=8)
    np.testing.assert_allclose(np.asarray(part + reference.swiglu(x, *shared)),
                               np.asarray(want), atol=5e-5)
    text = jax.jit(lambda *a: dropless_moe(*a, first_expert=8, **kw)).lower(
        x, router, wg[sl], wu[sl], wd[sl]).as_text()
    assert "stablehlo.case" in text or "stablehlo.if" in text
    # a chip that holds every expert has one path, as it had
    whole = jax.jit(lambda *a: dropless_moe(*a, **kw)).lower(x, router, wg, wu, wd).as_text()
    assert "stablehlo.case" not in whole and "stablehlo.if" not in whole


def test_the_sigmoid_router_weighs_scores_and_softmax_stays_as_it_was():
    x, router, wg, wu, wd, _ = _moe_operands(E=8)
    kw = dict(n_experts=8, top_k=2, scale=2.5)
    sig, _, chosen = dropless_moe(x, router, wg, wu, wd, score="sigmoid", **kw)
    soft, _, chosen_soft = dropless_moe(x, router, wg, wu, wd, **kw)
    named, _, _ = dropless_moe(x, router, wg, wu, wd, score="softmax", **kw)
    np.testing.assert_array_equal(np.asarray(soft), np.asarray(named))
    # both are monotone in the logit: the same experts, other weights
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(chosen_soft))
    assert float(jnp.abs(sig - soft).max()) > 1e-3
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x @ router)
        top = jnp.take_along_axis(s, chosen, axis=1)
        weight = 2.5 * top / (top.sum(axis=1, keepdims=True) + 1e-20)
        each = jnp.stack([(jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e] for e in range(8)], 1)
        want = (jnp.take_along_axis(each, chosen[..., None], axis=1) * weight[..., None]).sum(1)
    np.testing.assert_allclose(np.asarray(sig), np.asarray(want), atol=2e-5)


def test_a_model_that_holds_a_share_computes_that_share_and_says_so():
    """8 of 32 experts held (the glue's `experts_held`): the engine serves the
    reference given the same share, and a decode step computes about a
    quarter of the pairs its routers chose."""
    config = dict(SMALL, n_routed_experts=8)
    config["published"] = dict(SMALL["published"], n_routed_experts=32)
    model, variables = build(config, seed=3)
    assert model.cfg.experts_held == (0, 8) and model.cfg.sparse_experts == 32
    assert variables["params"]["layers_1"]["mlp"]["router"].shape == (64, 32)
    assert variables["params"]["layers_1"]["mlp"]["experts_gate"].shape == (8, 64, 32)
    engine = ServeEngine(model, variables, slots=3, block_size=BS, pool_blocks=40,
                         prefill_chunk_tokens=16, min_bucket=8)
    prompts = {f"r{i}": tokens_of(20 + 7 * i, 50 + i) for i in range(2)}
    for rid, p in prompts.items():
        engine.submit(p, 10, rid=rid)
    done = engine.run(max_steps=400)
    for rid, p in prompts.items():
        full = np.concatenate([p, np.asarray(done[rid].tokens[:-1], np.int32)])
        want = reference_logits(variables, full, 10, config)
        assert correctness.chosen_gap(want, done[rid].tokens) <= 1e-4
    moe = engine.metrics.snapshot()["moe"]
    assert 0 < moe["assignments_total"] < 0.6 * moe["routed_total"]


# --- (vii) the norms' three placements ---------------------------------------

def test_the_other_configurations_norms_stand_where_they_stood():
    """`post_norm` is still the output alone (Olmo's, which the hybrid glue
    passes), no flag still the input alone (Laguna's and the dense models'),
    and the two placements cannot both be asked for."""
    spec = (LayerSpec(),)
    base = dict(vocab_size=32, d_model=16, n_layers=1, n_heads=2, d_ff=32, max_seq_len=16,
                use_flash=False, layers=spec)
    x = jnp.zeros((1, 4), jnp.int32)
    names = lambda **kw: set(TransformerLM(TransformerConfig(**base, **kw)).init(
        jax.random.PRNGKey(0), x)["params"]["layers_0"])
    assert names() == names(post_norm=True) == {"attn", "mlp", "attn_norm", "mlp_norm"}
    assert names(sandwich_norm=True) == {"attn", "mlp", "attn_norm", "mlp_norm",
                                         "attn_post_norm", "mlp_post_norm"}
    # output alone: scaling the input of the block scales nothing it norms away
    cfg = TransformerConfig(**base, post_norm=True)
    block = TransformerLM(cfg)
    v = block.init(jax.random.PRNGKey(0), x)
    pre = TransformerLM(TransformerConfig(**base))
    assert not np.allclose(np.asarray(block.apply(v, x + 1)), np.asarray(pre.apply(v, x + 1)))
    with pytest.raises(ValueError, match="two placements"):
        TransformerConfig(**base, post_norm=True, sandwich_norm=True)
    # a model without a pattern reads neither flag
    plain = dict(base, layers=None)
    assert set(TransformerLM(TransformerConfig(**plain, sandwich_norm=True)).init(
        jax.random.PRNGKey(0), x)["params"]["layers_0"]) == {
            "attn", "mlp", "attn_norm", "mlp_norm"}


# --- (viii) the fourth kind beside the others ---------------------------------

@pytest.fixture(scope="module")
def mixed():
    """A full layer, a latent one and a linear one in one model."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=3, n_heads=2, d_ff=64, max_seq_len=64,
        use_flash=False,
        layers=(LayerSpec("full"), LayerSpec("latent"), LayerSpec("linear")),
        latent_q_rank=8, latent_kv_rank=16, latent_nope_dim=8, latent_rope_dim=4,
        latent_v_dim=8, linear_heads=2, linear_key_dim=8, linear_value_dim=8,
    )
    model = TransformerLM(cfg)
    return model, jax.jit(model.init)(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_latent_blocks_are_allocated_on_write_and_freed_on_retire_beside_other_kinds(mixed):
    model, _ = mixed
    assert model.cfg.cache_kinds == ("full", "linear", "latent")
    cache = PagedKVCache(model, 2, block_size=4, chunk_tokens=8)
    tree = cache.tree
    assert set(tree["layers_0"]["attn"]) == {"k", "v"}
    assert set(tree["layers_1"]) == {"latent_attn"}
    assert tree["layers_1"]["latent_attn"]["latent"].shape == (32, 4, 20)
    assert set(tree["layers_2"]["linear_attn"]) == {"state", "conv"}
    assert (cache.full_layers, cache.latent_layers, cache.linear_layers) == (1, 1, 1)
    slot = cache.allocate()
    assert cache.live_blocks == 0 and latent_gauge(cache)[0] == 0  # nothing written yet
    assert cache.ensure_blocks(slot, 9)
    assert cache.live_blocks == latent_gauge(cache)[0] == 3
    full, state, latent = cache.tables()
    # the latent kind rides the full kind's table: a block id names the same
    # tokens in every layer that keeps every token
    np.testing.assert_array_equal(full, latent)
    assert state.shape == (2, 1) and full.shape == (2, 16)
    assert not np.shares_memory(full, latent)
    itemsize = 4
    assert latent_gauge(cache)[1] == 4 * 20 * itemsize
    assert cache.bytes_per_block == 2 * 4 * 2 * 16 * itemsize + latent_gauge(cache)[1]
    assert cache.bytes_live == 3 * cache.bytes_per_block + cache.state_bytes_per_block
    assert cache.free(slot) == 3
    assert cache.live_blocks == latent_gauge(cache)[0] == 0
    assert (cache.tables()[2] == cache.invalid_block).all()
    with pytest.raises(ValueError, match="no int8 form"):
        init_paged_cache(model, 8, 4, quantized=True, state_blocks=2)


def test_a_model_of_three_kinds_serves_its_own_forward(mixed):
    """Each layer takes its kind's table out of the tuple the engine hands
    the programs: every served token is the cache-free forward's choice
    over the tokens before it."""
    model, variables = mixed
    engine = ServeEngine(model, variables, slots=2, block_size=4, prefill_chunk_tokens=8,
                         min_bucket=4)
    assert set(engine.metrics.decode_layer_paths) == {"full", "linear", "latent"}
    prompt = np.random.default_rng(0).integers(0, 64, (13,), dtype=np.int32)
    rid = engine.submit(prompt, 6)
    got = engine.run(max_steps=100)[rid].tokens
    full = np.concatenate([prompt, np.asarray(got[:-1], np.int32)])
    logits = np.asarray(jax.jit(model.apply)(variables, jnp.asarray(full)[None]))[0, -6:]
    assert correctness.chosen_gap(logits, got) <= 1e-4


def test_a_model_without_latent_layers_gets_the_tables_it_had():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2, n_kv_heads=1,
                            d_ff=64, max_seq_len=32, use_flash=False)
    cache = PagedKVCache(TransformerLM(cfg), 2, block_size=4)
    assert cache.kinds == ("full",) and cache.latent_layers == 0 and "latent" not in cache.avals
    assert latent_gauge(cache)[1] == 0 and latent_gauge(cache)[0] == 0
    assert isinstance(cache.tables(), np.ndarray)
    assert cache.bytes_per_block == 2 * 2 * 4 * 1 * 16 * 4
    assert cache.dense_bytes_per_request == 2 * 2 * 32 * 1 * 16 * 4


# --- (ix) what is not carried is refused --------------------------------------

REFUSED = {
    "kv_quant": dict(kv_quant=True),
    "mesh": dict(mesh=object()),
    "role": dict(role="prefill"),
    "precompiled": dict(precompiled={"step": object()}),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_a_latent_model_cannot_be_served_with_is_refused(small, what):
    model, variables = small
    with pytest.raises(ValueError, match="latent layers cannot be served with " + what):
        ServeEngine(model, variables, slots=2, block_size=BS, pool_blocks=40,
                    prefill_chunk_tokens=16, min_bucket=8, **REFUSED[what])


def test_a_latent_model_is_served_with_the_prefix_cache(small):
    """Carried since PR 38 (`tests/test_hyper_latent_moe.py` holds the
    attach, the copy and the reclaim): a second prompt adopts the first
    one's head and decodes what it decodes alone."""
    model, variables = small
    head = tokens_of(44, 50)
    prompts = [np.concatenate([head, tokens_of(9, 51 + i)]) for i in range(2)]
    tokens = {}
    for shared in (False, True):
        engine = ServeEngine(model, variables, slots=2, block_size=BS, pool_blocks=40,
                             prefill_chunk_tokens=16, min_bucket=8, prefix_cache=shared)
        for i, prompt in enumerate(prompts):
            engine.submit(prompt, 4, rid=f"r{i}")
            engine.run()
        tokens[shared] = [engine.completions[f"r{i}"].tokens for i in range(2)]
    assert engine.prefix.stats()["prefix_tokens_reused"] >= 44
    assert tokens[True] == tokens[False]


def test_a_pattern_that_contradicts_itself_is_refused(small):
    base = dict(n_layers=1, layers=(LayerSpec("latent"),))
    with pytest.raises(ValueError, match="latent_q_rank"):
        TransformerConfig(**base)
    with pytest.raises(ValueError, match="latent_q_rank"):
        TransformerConfig(**base, latent_q_rank=8, latent_kv_rank=8, latent_nope_dim=4,
                          latent_rope_dim=3, latent_v_dim=4)  # an odd rotary width
    with pytest.raises(ValueError, match="sparse_score"):
        TransformerConfig(sparse_score="tanh")
    model, variables = small
    x = jnp.zeros((2, 4), jnp.int32)
    cache = PagedKVCache(model, 2, block_size=BS)
    with pytest.raises(ValueError, match="block_tables and positions together"):
        model.apply({"params": variables["params"], "cache": cache.tree}, x, decode=True,
                    block_tables=jnp.asarray(cache.tables()), mutable=["cache"])
    with pytest.raises(ValueError, match="pre-built block-pool"):
        model.apply({"params": variables["params"]}, x, decode=True,
                    positions=jnp.zeros((2,), jnp.int32),
                    block_tables=jnp.asarray(cache.tables()), mutable=["cache"])
    for key in ("attention_bias", "tie_word_embeddings"):
        with pytest.raises(Exception, match="not carried"):
            modelglue.build_model(dict(SMALL, **{key: True}), M, remat=False)
    assert dataclasses.replace(model.cfg, sparse_score="softmax").sparse_score == "softmax"
