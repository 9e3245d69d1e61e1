"""A model with a layer pattern on the serve path, at a small size on the
CPU: window and full attention with per-layer head counts over two kinds of
paged K/V state, a per-head output gate, two rotary embeddings, dense and
dropless sparse MLPs with a shared expert. The program's model is built by
`bench_matrix/glue/sparse_window.py` from a configuration in the published
file's own keys, and compared with `bench_matrix/reference/sparse_window.py`
on seeded weights in float32: the test of the layer's equations."""

import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_matrix import correctness, modelglue
from bench_matrix.glue import sparse_window as glue
from bench_matrix.reference import sparse_window as reference
from pytorch_distributed_example_tpu.ops import (
    gather_paged_kv,
    paged_decode_attention,
    paged_window_span,
)
from pytorch_distributed_example_tpu.parallel.expert_parallel import dropless_moe
from pytorch_distributed_example_tpu.serve import ServeEngine
from pytorch_distributed_example_tpu.serve.cache import PagedKVCache, init_paged_cache

ROOT = Path(__file__).resolve().parents[1]
PUBLISHED = json.loads(
    (ROOT / "bench_matrix" / "configs" / "laguna-xs.2-d5.json").read_text())
WINDOW, BS, M = 8, 4, 160
# the published file cut to a toy: every mechanism, no width of the model's
SMALL = dict(
    PUBLISHED, hidden_size=64, head_dim=16, num_attention_heads=6,
    num_key_value_heads=2, num_attention_heads_per_layer=[6, 8, 8, 8, 6],
    intermediate_size=96, vocab_size=128, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    sliding_window=WINDOW,
    dtype={"weights": "float32", "activations": "float32", "kv_cache": "float32"},
)
SMALL["rope_parameters"] = copy.deepcopy(PUBLISHED["rope_parameters"])
# YaRN's ramp has to lie inside a 8-value rotation: a short original context
SMALL["rope_parameters"]["full_attention"].update(
    original_max_position_embeddings=16, factor=8.0)
# the reference alone routes every token, unless a test asks otherwise
# (asked, at this size: 8 experts share the probability 256 share there)
SMALL["model"] = dict(SMALL["model"])
CHECK = dict(SMALL["model"].pop("check"), tie_margin=0.05, replay={
    "block_size": BS, "prefill_chunk_tokens": 8, "max_seq_len": M})
ASKING = dict(SMALL["model"], check=CHECK)  # a `model` group that asks
LIMITS = {"max_rel": 1e-4, "rms_rel": 1e-4}
SPARSE_LAYERS, TOP_K = 4, 2


@pytest.fixture(scope="module")
def small():
    model = modelglue.build_model(SMALL, M, remat=False)
    return model, modelglue.make_variables(model, SMALL, seed=7)


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, SMALL["vocab_size"], (n,), dtype=np.int32)


def reference_logits(variables, tokens, last, config=SMALL, **kw):
    emb, layers, norm, w_out = glue.reference_parts(variables)
    return np.asarray(reference.logits(tokens, emb, layers, norm, w_out, config,
                                       last=last, **kw))


# --- (i) the equations ------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_cache_free_forward_is_the_reference(small, seed):
    """Every position of a sequence eight windows long: inside the first
    window, at its edge, and past it (one length, so one compilation)."""
    model, variables = small
    length = 64
    tokens = tokens_of(length, seed)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    out = correctness.compare(got, reference_logits(variables, tokens, length), LIMITS)
    assert out["ok"], out


def test_the_pattern_is_what_the_configuration_says(small):
    model, variables = small
    cfg = model.cfg
    assert cfg.window_layers == (False, True, True, True, False)
    assert cfg.sparse_layers == (1, 2, 3, 4)
    assert [cfg.layer(i).n_heads for i in range(5)] == [6, 8, 8, 8, 6]
    assert cfg.layer(0).rope.yarn is not None and cfg.layer(0).rope.rotary_fraction == 0.5
    assert cfg.layer(1).rope.yarn is None and cfg.layer(1).rope.theta == 10000
    p = variables["params"]
    assert p["layers_0"]["attn"]["q_proj"]["kernel"].shape == (64, 6 * 16)
    assert p["layers_1"]["attn"]["q_proj"]["kernel"].shape == (64, 8 * 16)
    assert p["layers_1"]["attn"]["head_gate"]["kernel"].shape == (64, 8)
    assert "gate_proj" in p["layers_0"]["mlp"] and "router" in p["layers_4"]["mlp"]
    assert p["layers_2"]["mlp"]["experts_down"].shape == (8, 32, 64)
    n = sum(a.size for a in jax.tree_util.tree_leaves(p))
    assert n == glue.param_count(SMALL)


@pytest.mark.parametrize("what", ["window", "gate", "scale", "shared", "rope"])
def test_the_comparison_sees_each_mechanism(small, what):
    """With one mechanism changed on the reference's side alone the logits
    no longer agree: the window by ONE key either way, the gate, the routing
    scale, the shared expert, the partial rotation."""
    model, variables = small
    tokens = tokens_of(64, 9)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    changed = {
        "window": [dict(SMALL, sliding_window=WINDOW - 1), dict(SMALL, sliding_window=WINDOW + 1)],
        "gate": [dict(SMALL, gating=False)],
        "scale": [dict(SMALL, moe_routed_scaling_factor=2.0)],
        "rope": [dict(SMALL, rope_parameters=dict(
            SMALL["rope_parameters"], full_attention=dict(
                SMALL["rope_parameters"]["full_attention"], partial_rotary_factor=1.0)))],
    }
    if what == "gate":
        emb, layers, norm, w_out = glue.reference_parts(variables)
        layers = [{k: v for k, v in w.items() if k != "w_head_gate"} for w in layers]
        wants = [np.asarray(reference.logits(tokens, emb, layers, norm, w_out, SMALL, last=64))]
    elif what == "shared":
        emb, layers, norm, w_out = glue.reference_parts(variables)
        layers = [dict(w, **({"shared_down": 0 * w["shared_down"]} if "router" in w else {}))
                  for w in layers]
        wants = [np.asarray(reference.logits(tokens, emb, layers, norm, w_out, SMALL, last=64))]
    else:
        wants = [reference_logits(variables, tokens, 64, config=c) for c in changed[what]]
    for want in wants:
        assert not correctness.compare(got, want, {"max_rel": 1e-3, "rms_rel": 1e-3})["ok"]


def test_the_reference_s_diagnostics_tell_a_flipped_choice_from_a_wrong_layer(small):
    """What `PERF.md`'s routing-flip count was made with: the reference records
    its choices and margins, takes another system's choices in their place,
    and computes its expert products or its K/V in a lower precision."""
    model, variables = small
    tokens = tokens_of(64, 5)
    got, inter = model.apply(variables, jnp.asarray(tokens)[None], mutable=["intermediates"])
    chosen = {i: inter["intermediates"][f"layers_{i}"]["mlp"]["moe_chosen"][0][0]
              for i in model.cfg.sparse_layers}
    record = []
    want = reference_logits(variables, tokens, 64, record=record)
    assert [r["layer"] for r in record] == [1, 2, 3, 4]
    for r in record:  # float32 on both sides: the same experts
        np.testing.assert_array_equal(np.sort(np.asarray(r["chosen"]), 1),
                                      np.sort(np.asarray(chosen[r["layer"]]), 1))
        assert r["margin"].shape == (64,) and float(r["margin"].min()) >= 0
    told = reference_logits(variables, tokens, 64, routing=chosen)
    assert correctness.compare(told, want, LIMITS)["ok"]
    # every token sent to its two LEAST likely experts: another function
    last = {i: jnp.argsort(jnp.asarray(np.random.default_rng(i).random((64, 8))))[:, :2]
            for i in chosen}
    assert not correctness.compare(
        reference_logits(variables, tokens, 64, routing=last), want, LIMITS)["ok"]
    for kw in ({"expert_dtype": jnp.float8_e4m3fn}, {"kv_dtype": jnp.float8_e4m3fn}):
        low = reference_logits(variables, tokens, 64, **kw)
        assert not correctness.compare(low, want, {"max_rel": 1e-3, "rms_rel": 1e-3})["ok"]
    assert correctness.compare(got[0], want, LIMITS)["ok"]


# --- (ii) chunked prefill and decode through the two pools ------------------

class Probe:
    """Keeps every prefill chunk's (start, its real tokens, logits)."""

    def __init__(self, program):
        self.program, self.chunks = program, []

    def __call__(self, params, tree, chunk, bt_row, start):
        tree, logits = self.program(params, tree, chunk, bt_row, start)
        tokens = np.asarray(chunk)[0]
        self.chunks.append((int(start), tokens[tokens >= 0], np.asarray(logits)))
        return tree, logits


def poison_free_window_blocks(engine):
    """Thousands in every window-layer block no row holds: a key attended
    after its block was handed back shows in the logits (a masked key has
    probability 0 exactly; NaN would pass through that product)."""
    free = np.asarray(engine.cache._window_free, np.int32)
    if not len(free):
        return
    for i, windowed in enumerate(engine.cfg.window_layers):
        if windowed:
            kv = engine.cache.tree[f"layers_{i}"]["attn"]
            for name in ("k", "v"):
                kv[name] = kv[name].at[free].set(1e3)


@pytest.fixture(scope="module")
def served(small):
    """One engine run: a 45-token prompt (five and a half windows, chunks of
    at most 10 that cross window and block edges) decoded for 25 tokens,
    with shorter requests coming and going beside it so that the window
    blocks it hands back are taken by other rows in between."""
    model, variables = small
    engine = ServeEngine(model, variables, slots=3, block_size=BS, pool_blocks=96,
                         prefill_chunk_tokens=10, min_bucket=4)
    probe = engine._prefill_chunk = Probe(engine._prefill_chunk)
    prompt = tokens_of(45, 21)
    engine.submit(prompt, 25, rid="long")
    for i in range(6):
        engine.submit(tokens_of(7 + 5 * i, 30 + i), 3 + i, rid=f"short{i}")
    steps = held = 0
    per_step = []
    while engine.step():
        poison_free_window_blocks(engine)
        steps += 1
        held = max(held, max(len(b) for b in engine.cache._window_slot_blocks))
        per_step.append((engine.metrics.moe_assignments, len(engine._decoding)))
        assert steps < 500
    return {"engine": engine, "done": engine.completions, "prompt": prompt,
            "chunks": probe.chunks, "held": held, "per_step": per_step,
            "variables": variables}


def test_chunked_prefill_through_both_pools_gives_the_reference_s_logits(served):
    prompt = served["prompt"]
    # the long prompt's chunks, by their tokens (the scheduler shares a
    # step's budget, so they start where it left them)
    mine = [(s, t, lg) for s, t, lg in served["chunks"]
            if len(t) and np.array_equal(t, prompt[s:s + len(t)])]
    assert sum(len(t) for _, t, _ in mine) == 45 and len(mine) >= 4
    assert any(s % WINDOW and s % BS for s, _, _ in mine)  # off both edges
    want = reference_logits(served["variables"], prompt, 45)
    for start, t, lg in mine:
        out = correctness.compare(lg[:len(t)], want[start:start + len(t)], LIMITS)
        assert out["ok"], (start, out)


def test_decoded_tokens_are_the_reference_s_choice(served):
    """24 decoded positions: each chosen token sits at the reference's best
    logit of the full forward over prompt + tokens so far."""
    tokens = served["done"]["long"].tokens
    assert len(tokens) == 25
    full = np.concatenate([served["prompt"], np.asarray(tokens[:24], np.int32)])
    want = reference_logits(served["variables"], full, 25)
    assert correctness.chosen_gap(want, tokens) <= 1e-4


@pytest.mark.parametrize("rid", [f"short{i}" for i in range(6)])
def test_rows_that_took_recycled_blocks_decode_the_reference_s_tokens(served, rid):
    engine, done = served["engine"], served["done"][rid]
    i = int(rid[5:])
    prompt = tokens_of(7 + 5 * i, 30 + i)
    full = np.concatenate([prompt, np.asarray(done.tokens[:-1], np.int32)])
    want = reference_logits(served["variables"], full, len(done.tokens))
    assert correctness.chosen_gap(want, done.tokens) <= 1e-4
    assert engine.cache.window_blocks_recycled > 0


def test_a_row_never_holds_more_window_blocks_than_the_pool_gives_it(served):
    cache = served["engine"].cache
    # window 8 + chunk 10 over blocks of 4: 5 + 2
    assert cache.window_blocks_per_slot == 7 and cache.window_num_blocks == 21
    assert 0 < served["held"] <= 7
    assert cache.window_blocks_recycled > 10
    assert cache.window_live_blocks == 0 and cache.live_blocks == 0  # all retired


def test_every_assignment_of_every_live_row_is_computed(served):
    """Dropless: top_k x live rows x sparse layers on every decode step."""
    seen = [(a, rows) for a, rows in served["per_step"] if a]
    assert seen
    m = served["engine"].metrics
    assert m.moe_steps > 0 and m.moe_assignments_total % (TOP_K * SPARSE_LAYERS) == 0
    snap = m.snapshot()
    assert snap["moe"]["steps"] == m.moe_steps
    assert 1 <= snap["moe"]["experts_hit_mean"] <= 6
    assert snap["cache_pool"]["window_blocks_recycled"] > 0


def test_the_engine_fails_the_comparison_with_a_window_one_key_off(small):
    """The same traffic through a model whose window layers attend one key
    too many or too few does not give the reference's logits."""
    model, variables = small
    tokens = tokens_of(45, 21)
    want = reference_logits(variables, tokens, 45)
    for off in (-1, 1):
        wrong = modelglue.build_model(dict(SMALL, sliding_window=WINDOW + off), M, False)
        engine = ServeEngine(wrong, variables, slots=2, block_size=BS, pool_blocks=64,
                             prefill_chunk_tokens=12, min_bucket=4)
        probe = engine._prefill_chunk = Probe(engine._prefill_chunk)
        engine.submit(tokens, 2, rid="x")
        engine.run(max_steps=200)
        start, t, lg = probe.chunks[-1]
        assert start + len(t) == 45 and start > 2 * WINDOW
        assert not correctness.compare(
            lg[:len(t)], want[start:45], {"max_rel": 1e-3, "rms_rel": 1e-3})["ok"]


# --- (iii) the share of a chip ----------------------------------------------

def _moe_operands(T=24, D=64, F=32, E=8, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (
        jax.random.normal(ks[0], (T, D)), jax.random.normal(ks[1], (D, E)) * 0.3,
        jax.random.normal(ks[2], (E, D, F)) * 0.1, jax.random.normal(ks[3], (E, D, F)) * 0.1,
        jax.random.normal(ks[4], (E, F, D)) * 0.1,
    )


def test_the_parts_of_four_ranges_of_two_experts_add_up_to_the_layer():
    x, router, wg, wu, wd = _moe_operands()
    kw = dict(n_experts=8, top_k=2, scale=2.5)
    whole, stats, _ = dropless_moe(x, router, wg, wu, wd, **kw)
    parts, assigned = 0.0, 0
    ranged = jax.jit(lambda first, *a: dropless_moe(*a, first_expert=first, **kw))
    for first in range(0, 8, 2):
        sl = slice(first, first + 2)
        part, st, _ = ranged(first, x, router, wg[sl], wu[sl], wd[sl])
        parts, assigned = parts + part, assigned + int(st[0])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), atol=2e-5)
    assert assigned == int(stats[0]) == 24 * 2
    # and the whole is the reference's sum over experts, shared expert apart
    w = {"router": router, "experts_gate": wg, "experts_up": wu, "experts_down": wd,
         "shared_gate": jnp.zeros((64, 4)), "shared_up": jnp.zeros((64, 4)),
         "shared_down": jnp.zeros((4, 64))}
    with jax.default_matmul_precision("highest"):
        want, routed = reference.sparse_mlp(
            x, w, top_k=2, scale=2.5, first_expert=0, expert_dtype=None)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=2e-5)
    assert routed["chosen"].shape == (24, 2) and float(routed["margin"].min()) >= 0
    assert not routed["differs"].any() and not routed["refused"].any()


def test_a_model_that_holds_a_range_gives_that_range_s_part(small):
    """`experts_held` in the model: the module holds (first, count) experts
    of every sparse layer, its router stays 8 wide."""
    import dataclasses

    from pytorch_distributed_example_tpu.models.transformer import SparseMoE

    model, _ = small
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 64))
    whole = SparseMoE(dataclasses.replace(model.cfg, shared_d_ff=0))
    v = whole.init(jax.random.PRNGKey(1), x)
    y = whole.apply(v, x)
    total = 0.0
    for first in (0, 4):
        part = SparseMoE(dataclasses.replace(
            model.cfg, shared_d_ff=0, experts_held=(first, 4)))
        p = {k: (a[first:first + 4] if k.startswith("experts_") else a)
             for k, a in v["params"].items()}
        assert part.init(jax.random.PRNGKey(1), x)["params"]["router"].shape == (64, 8)
        total = total + part.apply({"params": p}, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(y), atol=2e-5)


# --- (iv) rows that route nowhere -------------------------------------------

def test_masked_rows_reach_no_expert_and_change_no_live_row():
    x, router, wg, wu, wd = _moe_operands(T=6)
    kw = dict(n_experts=8, top_k=2, scale=2.5)
    mask = jnp.asarray([True, False, True, False, False, True])
    y, stats, chosen = dropless_moe(x, router, wg, wu, wd, row_mask=mask, **kw)
    live = np.asarray(mask)
    y_live, stats_live, _ = dropless_moe(x[live], router, wg, wu, wd, **kw)
    np.testing.assert_array_equal(np.asarray(y)[live], np.asarray(y_live))  # bit for bit
    assert not np.asarray(y)[~live].any()
    assert stats.tolist() == stats_live.tolist() and int(stats[0]) == 3 * 2
    _, every, chosen_all = dropless_moe(x, router, wg, wu, wd, **kw)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(chosen_all))  # the router's pick
    assert int(every[0]) == 6 * 2 and int(every[1]) >= int(stats[1])


def test_a_step_with_parked_lanes_counts_live_rows_only(small):
    model, variables = small
    engine = ServeEngine(model, variables, slots=4, block_size=BS, pool_blocks=64,
                         prefill_chunk_tokens=12, min_bucket=4)
    engine.submit(tokens_of(9, 1), 6, rid="only")  # three lanes stay parked
    seen = []
    while engine.step():
        if engine.metrics.moe_steps:
            seen.append((engine.metrics.moe_assignments, list(engine.metrics.moe_experts_hit)))
    assert seen and all(a == TOP_K * 1 * SPARSE_LAYERS for a, _ in seen)
    assert all(len(h) == SPARSE_LAYERS and max(h) <= TOP_K for _, h in seen)


def test_the_padding_of_a_chunk_routes_nowhere(small):
    """A 5-token prompt in a 12-token chunk: the 7 padded rows are token -1
    to the program, and the chunk's counters are those of 5 rows."""
    from pytorch_distributed_example_tpu.serve.decode import paged_programs

    model, variables = small
    cache = PagedKVCache(model, 1, num_blocks=40, block_size=BS, chunk_tokens=12)
    slot = cache.allocate()
    cache.ensure_blocks(slot, 4, first_pos=0)
    chunk = np.full((1, 12), -1, np.int32)
    chunk[0, :5] = tokens_of(5, 2)

    def run(params, tree, chunk, bt):
        logits, vs = model.apply(
            {"params": params, "cache": tree}, jnp.maximum(chunk, 0), decode=True,
            positions=jnp.zeros((1,), jnp.int32), block_tables=bt,
            row_mask=chunk >= 0, mutable=["cache", "intermediates"])
        return logits, vs["intermediates"]

    logits, stats = jax.jit(run)(variables["params"], cache.tree, jnp.asarray(chunk),
                                 cache.tables(slice(0, 1)))
    for i in model.cfg.sparse_layers:
        assignments, hit = stats[f"layers_{i}"]["mlp"]["moe_stats"][0].tolist()
        assert assignments == 5 * TOP_K and 1 <= hit <= 8
    # and the program the engine runs is that: same logits for the 5 rows
    program = paged_programs(model, 0.0, None)[0]
    _, got = program(variables["params"], cache.tree, jnp.asarray(chunk),
                     cache.tables(slice(0, 1)), 0)
    np.testing.assert_allclose(np.asarray(got)[:5], np.asarray(logits)[0, :5], atol=1e-5)
    cache.free(slot)


# --- (v) the windowed decode kernel -----------------------------------------

DH, KBS, NB, NBLK = 128, 16, 40, 160


def windowed_reference(q, pool_k, pool_v, tables, lengths, window):
    B, H, _ = q.shape
    KV = pool_k.shape[2]
    kf, vf = gather_paged_kv(pool_k, pool_v, tables)
    pos = jnp.arange(kf.shape[1])[None, None, :]
    mask = (pos <= lengths[:, None, None]) & (pos > lengths[:, None, None] - window)
    qg = q.reshape(B, 1, KV, H // KV, DH)
    s = jnp.einsum("blkrd,bmkd->bkrlm", qg, kf) * DH ** -0.5
    s = jnp.where(mask[:, None, None], s.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(vf.dtype)
    return jnp.einsum("bkrlm,bmkd->blkrd", p, vf).reshape(B, H, DH)


KERNEL_CASES = {
    # window, lengths: rows shorter than the window, at it, windows that
    # start mid-page and at a page's first key, several compute blocks
    "shorter_than_the_window": (64, [3, 40, 62]),
    "at_the_window": (64, [63, 64, 65]),
    "starts_mid_page": (50, [100, 333, 639]),
    "starts_on_a_page": (48, [47 + 16, 47 + 160, 47 + 320]),
    "several_compute_blocks": (600, [610, 639, 5]),
    "one_key": (1, [0, 17, 300]),
}


@pytest.mark.parametrize("heads", [48, 64], ids=["H48", "H64"])
@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_the_windowed_kernel_is_the_masked_einsum(case, heads):
    window, lengths = KERNEL_CASES[case]
    KV = 8
    ks = jax.random.split(jax.random.PRNGKey(heads + window), 3)
    q = jax.random.normal(ks[0], (len(lengths), heads, DH))
    pool_k = jax.random.normal(ks[1], (NBLK, KBS, KV, DH))
    pool_v = jax.random.normal(ks[2], (NBLK, KBS, KV, DH))
    # a window layer's table: only the pages from the first attended key on
    # are held; those behind were handed back and are invalid
    rng = np.random.default_rng(window)
    ids = rng.permutation(NBLK)
    tables = np.full((len(lengths), NB), NBLK, np.int32)
    at = 0
    for b, n in enumerate(lengths):
        first = max(n - window + 1, 0) // KBS
        m = n // KBS + 1 - first
        tables[b, first:first + m] = ids[at:at + m]
        at += m
    lens = jnp.asarray(lengths, jnp.int32)
    got = paged_decode_attention(q, pool_k, pool_v, jnp.asarray(tables), lens,
                                 interpret=True, window=window)
    # the reference gathers through a table whose holes point at real pages
    dense = np.where(tables == NBLK, 0, tables)
    want = windowed_reference(q, pool_k, pool_v, jnp.asarray(dense), lens, window)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_a_parked_row_costs_the_windowed_kernel_nothing():
    q = jnp.ones((2, 8, DH))
    pool = jnp.ones((NBLK, KBS, 8, DH))
    tables = np.full((2, NB), NBLK, np.int32)
    tables[1, :3] = [5, 6, 7]
    out = paged_decode_attention(q, pool, pool, jnp.asarray(tables),
                                 jnp.asarray([NB * KBS - 1, 40], jnp.int32),
                                 interpret=True, window=32)
    assert not np.asarray(out[0]).any() and np.allclose(np.asarray(out[1]), 1.0)


@pytest.mark.parametrize("L,start", [(1, 0), (1, 37), (12, 0), (12, 30), (12, 150)])
def test_the_span_a_window_layer_gathers_holds_every_key_it_attends(L, start):
    first, n = paged_window_span(jnp.asarray([start]), L, WINDOW, BS, M // BS)
    first = int(first[0])
    assert 0 <= first and first + n <= M // BS
    lo, hi = max(start - WINDOW + 1, 0), min(start + L - 1, M - 1)
    assert first * BS <= lo and hi < (first + n) * BS
    assert n <= -(-(WINDOW + L - 1) // BS) + 1  # not the table's span


# --- (vi) the allocator ------------------------------------------------------

def test_window_blocks_behind_the_window_go_back_while_the_request_runs(small):
    model, _ = small
    cache = PagedKVCache(model, 2, num_blocks=64, block_size=BS, chunk_tokens=12)
    a = cache.allocate()
    assert cache.ensure_blocks(a, 11, first_pos=0)
    assert sorted(cache.window_slot_blocks(a)) == [0, 1, 2]
    assert cache.ensure_blocks(a, 23, first_pos=12)  # keys from 5 on: block 0 goes
    assert sorted(cache.window_slot_blocks(a)) == [1, 2, 3, 4, 5]
    assert cache.window_blocks_recycled == 1 and cache.window_tables[a, 0] == cache.window_invalid_block
    assert len(cache.slot_blocks(a)) == 6  # the full layers keep the context
    for pos in range(24, 60):  # decode: one position at a time
        assert cache.ensure_blocks(a, pos, first_pos=pos)
        held = sorted(cache.window_slot_blocks(a))
        assert held[0] == max(pos - WINDOW + 1, 0) // BS and held[-1] == pos // BS
        assert len(held) <= 3
    assert len(cache.slot_blocks(a)) == 15
    assert cache.bytes_live == (
        15 * cache.bytes_per_block + len(held) * cache.window_bytes_per_block)
    # 2 full layers and 3 window layers of 2 KV heads x 16, float32, K and V
    assert cache.bytes_per_block == 2 * 2 * BS * 2 * 16 * 4
    assert cache.window_bytes_per_block == 2 * 3 * BS * 2 * 16 * 4
    full, window = cache.tables(parked=[a])
    assert (full[a] == cache.invalid_block).all() and (window[a] == cache.window_invalid_block).all()
    assert cache.block_tables[a, 0] != cache.invalid_block  # the manager's own are untouched
    assert cache.free(a) == 15
    assert cache.window_live_blocks == 0 and cache.live_blocks == 0
    assert (cache.window_tables == cache.window_invalid_block).all()


def test_a_write_longer_than_the_pool_was_sized_for_is_refused(small):
    model, _ = small
    cache = PagedKVCache(model, 1, num_blocks=64, block_size=BS, chunk_tokens=4)
    slot = cache.allocate()
    with pytest.raises(RuntimeError, match="window blocks"):
        cache.ensure_blocks(slot, 40, first_pos=0)
    cache.free(slot)


def test_preemption_returns_both_kinds(small):
    """A full-layer pool too small for two long requests: the younger is
    preempted, its window blocks go back with its full ones, and both
    finish with the reference's tokens."""
    model, variables = small
    engine = ServeEngine(model, variables, slots=2, block_size=BS, pool_blocks=40,
                         prefill_chunk_tokens=12, min_bucket=4)
    prompts = {"a": tokens_of(60, 5), "b": tokens_of(58, 6)}
    for rid, p in prompts.items():
        engine.submit(p, 30, rid=rid)
    done = engine.run(max_steps=2000)
    assert engine.metrics.preempted > 0
    assert engine.cache.window_live_blocks == 0 and engine.cache.live_blocks == 0
    assert len(engine.cache._window_free) == engine.cache.window_num_blocks
    for rid, p in prompts.items():
        full = np.concatenate([p, np.asarray(done[rid].tokens[:-1], np.int32)])
        want = reference_logits(variables, full, 30)
        assert correctness.chosen_gap(want, done[rid].tokens) <= 1e-4


def test_a_model_without_window_layers_gets_the_single_kind_it_had():
    from pytorch_distributed_example_tpu.models.transformer import (
        TransformerConfig, TransformerLM,
    )

    model = TransformerLM(TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=3, max_seq_len=64))
    cache = PagedKVCache(model, 2, num_blocks=24, block_size=8)
    assert cache.window_layers == 0 and cache.window_num_blocks == 0
    assert set(cache.tree) == {"layers_0", "layers_1", "layers_2"}
    for layer in cache.tree.values():
        assert set(layer["attn"]) == {"k", "v"}
        assert layer["attn"]["k"].shape == (24, 8, 2, 8)
    assert jax.tree_util.tree_structure(cache.tree) == jax.tree_util.tree_structure(
        init_paged_cache(model, 24, 8))
    slot = cache.allocate()
    assert cache.ensure_blocks(slot, 20)
    tables = cache.tables()
    # one table, and a copy: the engine mutates its own while a program that
    # was handed this one is still queued
    assert isinstance(tables, np.ndarray) and np.array_equal(tables, cache.block_tables)
    assert not np.shares_memory(tables, cache.block_tables)
    assert cache.bytes_per_block == 2 * 3 * 8 * 2 * 8 * 4
    assert cache.bytes_live == 3 * cache.bytes_per_block
    assert cache.free(slot) == 3
    assert model.cfg.layer(0) is None and model.cfg.window_layers == (False,) * 3


# --- (vii) what is not carried is refused ------------------------------------

REFUSED = {
    "prefix_cache": dict(prefix_cache=True),
    "kv_quant": dict(kv_quant=True),
    "mesh": dict(mesh=object()),
    "role": dict(role="prefill"),
    "precompiled": dict(precompiled={"step": object()}),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_a_window_model_cannot_be_served_with_is_refused(small, what):
    model, variables = small
    with pytest.raises(ValueError, match="window layers cannot be served with " + what):
        ServeEngine(model, variables, slots=2, block_size=BS, pool_blocks=64,
                    prefill_chunk_tokens=12, min_bucket=4, **REFUSED[what])


def test_a_window_layer_says_once_that_flash_has_no_window(small):
    import dataclasses

    from pytorch_distributed_example_tpu.models.transformer import TransformerLM

    model, variables = small
    flash = TransformerLM(dataclasses.replace(model.cfg, use_flash=True))
    with pytest.warns(RuntimeWarning, match="flash kernel has no window"):
        got = jax.eval_shape(flash.apply, variables, jnp.zeros((1, 16), jnp.int32))
    assert got.shape == (1, 16, SMALL["vocab_size"])


def test_a_pattern_that_contradicts_itself_is_refused():
    from pytorch_distributed_example_tpu.models.transformer import LayerSpec, TransformerConfig

    with pytest.raises(ValueError, match="layer specs"):
        TransformerConfig(n_layers=2, layers=(LayerSpec(),))
    with pytest.raises(ValueError, match="window is unset"):
        TransformerConfig(n_layers=1, layers=(LayerSpec("window"),))
    with pytest.raises(ValueError, match="sparse_experts"):
        TransformerConfig(n_layers=1, layers=(LayerSpec(mlp="sparse"),))
    with pytest.raises(ValueError, match="kv_heads"):
        TransformerConfig(n_layers=1, n_heads=8, n_kv_heads=4, layers=(LayerSpec(n_heads=6),))
