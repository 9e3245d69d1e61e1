"""A model that mixes gated short-convolution layers 3:1 with grouped-query
attention (a norm a head, a tied head) over dense then sparse MLPs of which
this chip holds half the experts, on the serve path, at a small size on the
CPU: a state block a request that holds a two-token tail and nothing else
beside paged K/V, the conv's chunked and one-token forms. The program's
model is built by `bench_matrix/glue/conv_moe.py` from a configuration in
the published file's own keys, and compared with
`bench_matrix/reference/conv_moe.py` (no cache, no chunks) on seeded weights
in float32: the test of the layer's equations."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_matrix import correctness, modelglue
from bench_matrix.glue import conv_moe as glue
from bench_matrix.reference import conv_moe as reference
from pytorch_distributed_example_tpu.models.generate import generate, init_cache
from pytorch_distributed_example_tpu.models.transformer import (
    CACHE_KINDS,
    STATE_KINDS,
    LayerSpec,
    TransformerConfig,
    TransformerLM,
    state_block_shapes,
)
from pytorch_distributed_example_tpu.ops import (
    gather_paged_kv,
    paged_chunk_attention,
    paged_decode_attention,
    paged_kernel,
)
from pytorch_distributed_example_tpu.ops.paged_attention import (
    pack_pool_heads,
    pool_head_pack,
    pool_kv_shape,
    unpack_pool_heads,
)
from pytorch_distributed_example_tpu.serve import ServeEngine
from pytorch_distributed_example_tpu.serve.cache import PagedKVCache, init_paged_cache

ROOT = Path(__file__).resolve().parents[1]
PUBLISHED = json.loads(
    (ROOT / "bench_matrix" / "configs" / "lfm2-8b-a1b-e16.json").read_text())
BS, M = 4, 160
F32 = {"weights": "float32", "activations": "float32", "kv_cache": "float32",
       "logits": "float32", "router": "float32", "conv_mixer": "float32",
       "conv_tail": "float32"}
# the published file cut to a toy: the pattern's first eight layers (conv
# conv full conv conv conv full conv), two dense then six sparse, half of 8
# experts held, top 2; no width of the model's
WHOLE = dict(
    PUBLISHED["published"], hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=96, moe_intermediate_size=32, vocab_size=128, num_hidden_layers=8,
    layer_types=PUBLISHED["layer_types"][:8], num_experts=8, num_experts_per_tok=2)
SMALL = dict(
    PUBLISHED, **dict(WHOLE, num_experts=4), published=WHOLE, dtype=F32,
    model=dict(PUBLISHED["model"], check=None))
LIMITS = {"max_rel": 1e-4, "rms_rel": 1e-4}
LOOSE = {"max_rel": 1e-3, "rms_rel": 1e-3}
CONV = [i for i, kind in enumerate(SMALL["layer_types"]) if kind == "conv"]


def build(**changed):
    """The toy through the glue."""
    return modelglue.build_model(dict(SMALL, **changed), M, remat=False)


@pytest.fixture(scope="module")
def small():
    model = build()
    return model, modelglue.make_variables(model, SMALL, seed=11)


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, SMALL["vocab_size"], (n,), dtype=np.int32)


def reference_logits(variables, tokens, last, config=SMALL, **kw):
    emb, layers, norm, w_out = glue.reference_parts(variables)
    return np.asarray(reference.logits(tokens, emb, layers, norm, w_out, config,
                                       last=last, **kw))


# --- (i) the equations ------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_cache_free_forward_is_the_reference(small, seed):
    model, variables = small
    tokens = tokens_of(90, seed)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    out = correctness.compare(got, reference_logits(variables, tokens, 90), LIMITS)
    assert out["ok"], out


def test_the_pattern_is_what_the_configuration_says(small):
    model, variables = small
    cfg = model.cfg
    assert cfg.conv_layers == (0, 1, 3, 4, 5, 7) and cfg.cache_kinds == ("full", "conv")
    assert cfg.linear_layers == () and cfg.sparse_layers == (2, 3, 4, 5, 6, 7)
    assert cfg.qk_head_norm and not cfg.qk_norm and cfg.tie_embeddings
    assert cfg.experts_held == (0, 4) and cfg.sparse_experts == 8 and cfg.conv_taps == 3
    assert cfg.sparse_choice_bias and cfg.sparse_norm_eps == 1e-6 and not cfg.shared_d_ff
    p = variables["params"]
    assert "attn" not in p["layers_0"] and "gated_conv" not in p["layers_2"]
    conv = p["layers_0"]["gated_conv"]
    assert conv["in_proj"]["kernel"].shape == (64, 3 * 64)
    assert conv["out_proj"]["kernel"].shape == (64, 64) and conv["conv"].shape == (3, 64)
    attn = p["layers_2"]["attn"]
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (16,)
    assert attn["k_proj"]["kernel"].shape == (64, 2 * 16)
    mlp = p["layers_2"]["mlp"]
    assert mlp["router"].shape == (64, 8) and mlp["router_bias"].shape == (8,)
    assert mlp["experts_gate"].shape == (4, 64, 32) and "shared_expert" not in mlp
    n = sum(a.size for a in jax.tree_util.tree_leaves(p))
    assert n == glue.param_count(SMALL)


def test_the_tied_head_shares_one_array(small):
    """No `lm_head` in the tree: the logits are the final norm's output
    against the embedding itself, on both sides; moving the embedding moves
    both its uses."""
    model, variables = small
    p = variables["params"]
    assert "lm_head" not in p and set(p) >= {"tok_embed", "final_norm"}
    emb, _, _, w_out = glue.reference_parts(variables)
    assert w_out.shape == emb.shape[::-1] and bool((w_out == emb.T).all())
    tokens = tokens_of(20, 3)
    moved = jax.tree_util.tree_map(lambda a: a, p)
    moved["tok_embed"] = {"embedding": p["tok_embed"]["embedding"] * 1.5}
    got = model.apply({"params": moved}, jnp.asarray(tokens)[None])[0]
    assert correctness.compare(got, reference_logits({"params": moved}, tokens, 20), LIMITS)["ok"]
    untied = reference_logits(variables, tokens, 20, fault="untied_head")
    assert not correctness.compare(
        model.apply(variables, jnp.asarray(tokens)[None])[0], untied, LOOSE)["ok"]
    with pytest.raises(ValueError, match="layer pattern"):
        TransformerLM(TransformerConfig(
            vocab_size=16, d_model=16, n_layers=1, n_heads=2, tie_embeddings=True,
            use_flash=False)).init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_the_per_head_norm_is_not_the_whole_projection_s(small):
    """The two norms of q and k differ, on both sides: the program with
    `qk_norm` in place of `qk_head_norm` (scales tiled to the projection),
    and the reference with its `whole_norm` fault, each leave the other."""
    model, variables = small
    tokens = tokens_of(40, 9)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    want = reference_logits(variables, tokens, 40)
    assert correctness.compare(got, want, LIMITS)["ok"]
    whole = reference_logits(variables, tokens, 40, fault="whole_norm")
    assert not correctness.compare(got, whole, LOOSE)["ok"]
    other = TransformerLM(dataclasses.replace(model.cfg, qk_head_norm=False, qk_norm=True))
    p = jax.tree_util.tree_map(lambda a: a, variables["params"])
    for i in (2, 6):
        attn = p[f"layers_{i}"]["attn"]
        attn["q_norm"] = {"scale": jnp.tile(attn["q_norm"]["scale"], 4)}
        attn["k_norm"] = {"scale": jnp.tile(attn["k_norm"]["scale"], 2)}
    swapped = other.apply({"params": p}, jnp.asarray(tokens)[None])[0]
    assert correctness.compare(swapped, whole, LIMITS)["ok"]
    assert not correctness.compare(swapped, want, LOOSE)["ok"]
    with pytest.raises(ValueError, match="two norms"):
        dataclasses.replace(model.cfg, qk_norm=True)


def _reference_with(variables, tokens, change, **kw):
    emb, layers, norm, w_out = glue.reference_parts(variables)
    layers = [change(dict(w)) for w in layers]
    return np.asarray(reference.logits(tokens, emb, layers, norm, w_out, SMALL,
                                       last=len(tokens), **kw))


def _newest_tap_only(w):
    if "conv" in w:
        w["conv"] = w["conv"].at[:-1].set(0.0)
    return w


def _thirds_in_another_order(w):
    if "w_in" in w:
        b, c, u = jnp.split(w["w_in"], 3, axis=1)
        w["w_in"] = jnp.concatenate([c, b, u], axis=1)
    return w


def _no_bias(w):
    if "router_bias" in w:
        w["router_bias"] = w["router_bias"] * 0.0
    return w


MECHANISMS = {
    "older_taps": dict(change=_newest_tap_only),
    "order_of_the_thirds": dict(change=_thirds_in_another_order),
    "router_bias": dict(change=_no_bias),
    "rope": dict(config=dict(SMALL, rope_theta=1e4)),
}


@pytest.mark.parametrize("what", sorted(MECHANISMS))
def test_the_comparison_sees_each_mechanism(small, what):
    """With one term changed on the reference's side alone the logits no
    longer agree: the conv's older taps, which third of the input
    projection gates, the expert bias in the choice, the rope's base."""
    model, variables = small
    tokens = tokens_of(40, 9)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    case = MECHANISMS[what]
    if "change" in case:
        want = _reference_with(variables, tokens, case["change"])
    else:
        want = reference_logits(variables, tokens, 40, config=case["config"])
    assert not correctness.compare(got, want, LOOSE)["ok"]


def test_the_weights_are_normalised_with_1e_6():
    """sum + 1e-6, not + 1e-20: with every score near sigmoid(-12) = 6e-6
    the chosen two sum to 1.2e-5 and the published 1e-6 takes a thirteenth
    off the weights; program and reference agree with each other there, and
    the program at 1e-20 does not."""
    from pytorch_distributed_example_tpu.parallel.expert_parallel import dropless_moe

    rng = np.random.default_rng(5)
    D, F, E, K, T = 32, 16, 8, 2, 20
    f = lambda *shape, s=1.0: jnp.asarray(rng.normal(size=shape) * s, jnp.float32)
    n = f(T, D, s=0.1).at[:, 0].set(1.0)
    w = {"router": f(D, E, s=0.1).at[0].set(-12.0), "router_bias": f(E, s=0.05),
         "experts_gate": f(E, D, F), "experts_up": f(E, D, F), "experts_down": f(E, F, D)}
    want, _ = reference.sparse_ffn(n, w, top_k=K, scale=1.0, first_expert=0)
    run = lambda eps: dropless_moe(
        n, w["router"], w["experts_gate"], w["experts_up"], w["experts_down"], n_experts=E,
        top_k=K, score="sigmoid", choice_bias=w["router_bias"], norm_eps=eps)[0]
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(run(1e-6) - want).max()) <= 1e-5 * scale
    assert float(jnp.abs(run(1e-20) - want).max()) >= 3e-2 * scale


# --- (ii) the share of the experts --------------------------------------------

def test_the_two_chips_parts_add_up_to_the_uncut_layer(small):
    """The guide's share test: the sparse layer's output for experts 0-3
    and for experts 4-7 (the router at its full 8 outputs, top 2, the
    weights normalised over both chosen whether held or not), added, is the
    uncut reference's whole layer; in the program's `dropless_moe` and in
    the reference's `sparse_ffn` alike."""
    from pytorch_distributed_example_tpu.parallel.expert_parallel import dropless_moe

    rng = np.random.default_rng(3)
    D, F, E, K, T = 64, 32, 8, 2, 50
    f = lambda *shape, s=1.0: jnp.asarray(rng.normal(size=shape) * s, jnp.float32)
    n = f(T, D)
    w = {"router": f(D, E, s=D ** -0.5), "router_bias": f(E, s=0.05),
         "experts_gate": f(E, D, F, s=D ** -0.5), "experts_up": f(E, D, F, s=D ** -0.5),
         "experts_down": f(E, F, D, s=F ** -0.5)}
    part = lambda first, count: {k: (v[first:first + count] if k.startswith("experts") else v)
                                 for k, v in w.items()}
    whole, routed = reference.sparse_ffn(n, w, top_k=K, scale=1.0, first_expert=0)
    halves = [reference.sparse_ffn(n, part(first, 4), top_k=K, scale=1.0, first_expert=first)
              for first in (0, 4)]
    np.testing.assert_allclose(
        np.asarray(halves[0][0] + halves[1][0]), np.asarray(whole), rtol=0, atol=1e-5)
    for _, said in halves:  # both chips route alike, over all eight
        np.testing.assert_array_equal(np.asarray(said["chosen"]), np.asarray(routed["chosen"]))
    assert float(jnp.abs(halves[0][0]).max()) > 1e-2 and float(jnp.abs(halves[1][0]).max()) > 1e-2
    system = []
    for first in (0, 4):
        held = part(first, 4)
        y, stats, chosen = dropless_moe(
            n, w["router"], held["experts_gate"], held["experts_up"], held["experts_down"],
            n_experts=E, top_k=K, scale=1.0, first_expert=first, score="sigmoid",
            choice_bias=w["router_bias"], norm_eps=1e-6)
        system.append(y)
        np.testing.assert_array_equal(
            np.sort(np.asarray(chosen), axis=-1), np.sort(np.asarray(routed["chosen"]), axis=-1))
    np.testing.assert_allclose(
        np.asarray(system[0] + system[1]), np.asarray(whole), rtol=0, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(system[0]), np.asarray(halves[0][0]), rtol=0, atol=2e-5)


def test_a_model_that_holds_every_expert_is_the_sum_of_two_that_hold_half():
    """Through the glue: the toy with all 8 experts held, and two chips'
    models that hold 0-3 and 4-7 of the same weights: in one sparse layer
    the two parts add up to the whole."""
    whole_cfg = dict(SMALL, num_experts=8)
    model = modelglue.build_model(whole_cfg, M, remat=False)
    variables = modelglue.make_variables(model, whole_cfg, seed=5)
    tokens = tokens_of(24, 8)
    want = reference_logits(variables, tokens, 24, config=whole_cfg)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    assert correctness.compare(got, want, LIMITS)["ok"]
    assert glue.param_count(whole_cfg) == sum(
        a.size for a in jax.tree_util.tree_leaves(variables["params"]))


# --- (iii) the serve path ----------------------------------------------------

class Probe:
    """Keeps every prefill chunk's (start, its real tokens, logits, padding)."""

    def __init__(self, program):
        self.program, self.chunks = program, []

    def __call__(self, params, tree, chunk, bt_row, start):
        tree, logits = self.program(params, tree, chunk, bt_row, start)
        tokens = np.asarray(chunk)[0]
        self.chunks.append((int(start), tokens[tokens >= 0], np.asarray(logits),
                            int((tokens < 0).sum())))
        return tree, logits


def poison_free_state_blocks(engine):
    """Thousands in every state block no request holds: a block read by a
    row that does not own it, or not read as zero by its next owner, shows
    in the logits."""
    free = np.asarray(engine.cache._state_free, np.int32)
    if not len(free):
        return
    for i in engine.cfg.conv_layers:
        leaves = engine.cache.tree[f"layers_{i}"]["gated_conv"]
        leaves["tail"] = leaves["tail"].at[free].set(1e3)


def serve(model, variables, requests, slots=3, chunk=8, poison=True, **kw):
    """One engine run over (rid, prompt, new tokens) requests."""
    engine = ServeEngine(model, variables, slots=slots, block_size=BS,
                         prefill_chunk_tokens=chunk, min_bucket=4, **kw)
    probe = engine._prefill_chunk = Probe(engine._prefill_chunk)
    for rid, prompt, n in requests:
        engine.submit(prompt, n, rid=rid)
    steps, live = 0, []
    while engine.step():
        if poison:
            poison_free_state_blocks(engine)
        live.append(engine.cache.state_live_blocks)
        steps += 1
        assert steps < 800
    return engine, probe.chunks, live


REQUESTS = [
    # a prompt over five chunks that ends inside a bucket; a short one that
    # comes and goes beside it; one that arrives when the first slot's
    # neighbour has retired, so a parked lane stands between the two live ones
    ("long", tokens_of(37, 21), 12),
    ("short", tokens_of(5, 22), 3),
    ("mid", tokens_of(19, 23), 9),
    ("late", tokens_of(11, 24), 14),
    ("last", tokens_of(8, 25), 6),
]


@pytest.fixture(scope="module")
def served(small):
    model, variables = small
    engine, chunks, live = serve(model, variables, REQUESTS)
    return {"engine": engine, "chunks": chunks, "live": live, "variables": variables,
            "done": engine.completions}


@pytest.mark.parametrize("rid", [r[0] for r in REQUESTS])
def test_chunked_prefill_over_the_state_block_gives_the_reference_s_logits(served, rid):
    prompt = dict((r[0], r[1]) for r in REQUESTS)[rid]
    n = len(prompt)
    mine = [(s, t, lg, pad) for s, t, lg, pad in served["chunks"]
            if len(t) and np.array_equal(t, prompt[s:s + len(t)])]
    assert sum(len(t) for _, t, _, _ in mine) == n
    if rid == "long":
        assert len(mine) >= 5 and mine[-1][3] > 0  # tail carried; padding
    want = reference_logits(served["variables"], prompt, n)
    for start, t, lg, _ in mine:
        out = correctness.compare(lg[:len(t)], want[start:start + len(t)], LIMITS)
        assert out["ok"], (start, out)


@pytest.mark.parametrize("rid", [r[0] for r in REQUESTS])
def test_decoded_tokens_are_the_reference_s_choice(served, rid):
    """Every decoded position: the chosen token sits at the reference's best
    logit of the full forward over prompt + tokens so far."""
    prompt = dict((r[0], r[1]) for r in REQUESTS)[rid]
    tokens = served["done"][rid].tokens
    full = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    want = reference_logits(served["variables"], full, len(tokens))
    assert correctness.chosen_gap(want, tokens) <= 1e-4


def test_several_rows_were_live_and_blocks_changed_hands(served):
    """The traffic above did what it was made for: three rows decoded at
    once, a free slot lay between two live ones, and requests took over
    state blocks that others had held (poisoned in between)."""
    live = served["live"]
    assert max(live) == 3 and live[-1] <= 1
    cache = served["engine"].cache
    assert cache.state_live_blocks == 0 and sorted(cache._state_free) == [0, 1, 2]
    assert (cache.state_table == cache.state_invalid_block).all()
    snap = served["engine"].metrics.snapshot()
    assert snap["decode"]["layer_paths"]["conv"] == [6, "conv_step"]
    assert snap["prefill"]["layer_paths"]["conv"] == [6, "conv_chunk"]
    assert snap["decode"]["layer_paths"]["full"] == [2, "gather"]  # heads of 16
    assert snap["cache_pool"]["state_blocks_live"] in (0, 1)


def test_generate_gives_the_served_tokens(small, served):
    model, variables = small
    for rid, prompt, n in REQUESTS[:2]:
        out = generate(model, variables, jnp.asarray(prompt)[None], n)
        assert np.asarray(out)[0].tolist() == served["done"][rid].tokens


def test_the_generate_cache_is_the_module_s_own(small):
    model, _ = small
    made = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 4), jnp.int32), decode=True)["cache"]
    built = init_cache(model, 2)
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), made) == \
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), built)
    assert set(built["layers_0"]) == {"gated_conv"} and set(built["layers_2"]) == {"attn"}
    assert built["layers_0"]["gated_conv"]["tail"].shape == (2, 2, 64)


def test_a_preempted_and_requeued_request_equals_an_undisturbed_one(small):
    """Fourteen K/V blocks under two requests that grow to ten and eight:
    the younger is preempted mid-decode, loses its state block (poisoned
    while free) and prefills again from 0."""
    model, variables = small
    requests = [("a", tokens_of(21, 31), 18), ("b", tokens_of(14, 32), 16)]
    alone = {}
    for request in requests:
        solo, _, _ = serve(model, variables, [request], slots=2)
        alone[request[0]] = solo.completions[request[0]].tokens
    engine = ServeEngine(model, variables, slots=2, block_size=BS, pool_blocks=M // BS,
                         prefill_chunk_tokens=8, min_bucket=4)
    # the pool must hold one max-length request; all but 14 blocks are taken away
    del engine.cache._free_blocks[14:]
    for rid, prompt, n in requests:
        engine.submit(prompt, n, rid=rid)
    steps = 0
    while engine.step():
        poison_free_state_blocks(engine)
        steps += 1
        assert steps < 800
    assert engine.metrics.preempted >= 1
    assert max(c.requeues for c in engine.completions.values()) >= 1
    for rid, tokens in alone.items():
        assert engine.completions[rid].tokens == tokens, rid


def test_a_block_taken_over_reads_zero_at_its_chunk_0(small):
    """One slot, two requests one after the other: the second takes the
    first's state block, poisoned or not, and gives what it gives alone."""
    model, variables = small
    first, second = ("x", tokens_of(13, 41), 5), ("y", tokens_of(9, 42), 7)
    engine, chunks, _ = serve(model, variables, [first, second], slots=1, poison=False)
    assert engine.cache.state_num_blocks == 1  # the same block both times
    alone, _, _ = serve(model, variables, [second], slots=1)
    assert engine.completions["y"].tokens == alone.completions["y"].tokens
    want = reference_logits(variables, second[1], 9)
    mine = [(s, t, lg) for s, t, lg, _ in chunks if np.array_equal(t, second[1][s:s + len(t)])]
    for start, t, lg in mine:
        assert correctness.compare(lg[:len(t)], want[start:start + len(t)], LIMITS)["ok"]


def test_padding_that_moved_the_tail_would_fail(small):
    """The engine with its chunk padding written as token 0 (as a model
    without sparse layers or state blocks gets it): the tail is taken
    behind the padded last chunk's padding and the decoded tokens leave the
    reference; so does the reference with a zero tail behind the prompt."""
    model, variables = small
    prompt = tokens_of(37, 21)
    engine = ServeEngine(model, variables, slots=1, block_size=BS, prefill_chunk_tokens=8,
                         min_bucket=8)
    assert engine._pad_id == -1
    engine._pad_id = 0
    engine.submit(prompt, 12, rid="p")
    tokens = engine.run(max_steps=400)["p"].tokens
    full = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    want = reference_logits(variables, full, len(tokens))
    assert correctness.chosen_gap(want, tokens) > 1e-3
    sound, _, _ = serve(model, variables, [("p", prompt, 12)], slots=1)
    tokens = sound.completions["p"].tokens
    full = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    assert correctness.chosen_gap(reference_logits(variables, full, 12), tokens) <= 1e-4
    faulty = reference_logits(variables, full, 12, fault="padded_tail", prompt=37)
    assert correctness.chosen_gap(faulty, tokens) > 1e-3


@pytest.mark.parametrize("fault", ["stale_tail", "neighbour_tail"])
def test_a_stale_tail_would_fail(small, served, fault):
    """The tail one token stale at every hand-over between chunks of 8 (or
    another request's tail there): the reference with that fault planted
    leaves the served chunks' logits by far more than the tolerance, in the
    chunk behind a hand-over; the first chunk has none and still agrees."""
    prompt = REQUESTS[0][1]
    mine = [(s, t, lg) for s, t, lg, _ in served["chunks"]
            if len(t) and np.array_equal(t, prompt[s:s + len(t)])]
    faulty = reference_logits(served["variables"], prompt, len(prompt), fault=fault, chunk=8)
    first = [c for c in mine if c[0] == 0][0]
    assert correctness.compare(first[2][:8], faulty[:8], LIMITS)["ok"]
    later = [c for c in mine if c[0] > 0]
    assert later
    for start, t, lg in later:
        assert not correctness.compare(lg[:len(t)], faulty[start:start + len(t)], LOOSE)["ok"]


def test_a_tail_kept_in_bfloat16_products_fails_the_comparison(small):
    """What the cell's limits are set against: the reference's own logits
    with the conv operator's products, or the router, in bfloat16."""
    _, variables = small
    tokens = tokens_of(80, 4)
    want = reference_logits(variables, tokens, 40)
    for kw in ({"mixer_dtype": jnp.bfloat16}, {"router_dtype": jnp.bfloat16}):
        low = reference_logits(variables, tokens, 40, **kw)
        assert not correctness.compare(low, want, LOOSE)["ok"], kw


REFUSED = {
    "prefix_cache": dict(prefix_cache=True),
    "kv_quant": dict(kv_quant=True),
    "precompiled": dict(precompiled={"anything": 1}),
    "role": dict(role="prefill"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_the_engine_refuses_for_conv_layers_what_it_refuses_for_linear_ones(small, what):
    model, variables = small
    with pytest.raises(ValueError, match="a model with conv layers cannot be served with") as e:
        ServeEngine(model, variables, slots=2, block_size=BS, **REFUSED[what])
    assert what in str(e.value)


def test_the_refusals_are_the_linear_layers_words():
    """One table of refusals for every kind whose layers keep a state block."""
    lin = TransformerLM(TransformerConfig(
        vocab_size=32, d_model=32, n_layers=2, n_heads=2, max_seq_len=32, use_flash=False,
        linear_heads=2, linear_key_dim=8, linear_value_dim=8,
        layers=(LayerSpec("linear"), LayerSpec("full"))))
    conv = TransformerLM(TransformerConfig(
        vocab_size=32, d_model=32, n_layers=2, n_heads=2, max_seq_len=32, use_flash=False,
        layers=(LayerSpec("conv"), LayerSpec("full"))))
    said = {}
    for name, model in (("linear", lin), ("conv", conv)):
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
        with pytest.raises(ValueError) as e:
            ServeEngine(model, variables, slots=2, block_size=BS, prefix_cache=True)
        said[name] = str(e.value)
    assert said["conv"] == said["linear"].replace("linear layers", "conv layers")


# --- (iv) the cache manager ---------------------------------------------------

def test_a_state_block_s_leaves_are_its_mixer_s_own(small):
    """A conv layer's block is a tail alone, a linear layer's a state and a
    conv tail: one kind of block, one table, whichever mixer names the
    leaves; a conv layer allocates no K/V pool."""
    model, _ = small
    assert STATE_KINDS == ("linear", "conv") and set(STATE_KINDS) < set(CACHE_KINDS)
    name, leaves = state_block_shapes(model.cfg, "conv")
    assert name == "gated_conv" and leaves == {"tail": ((2, 64), jnp.float32)}
    tree = init_paged_cache(model, 40, BS, state_blocks=3)
    for i in range(8):
        layer = tree[f"layers_{i}"]
        if i in CONV:
            assert set(layer) == {"gated_conv"} and set(layer["gated_conv"]) == {"tail"}
            assert layer["gated_conv"]["tail"].shape == (3, 2, 64)
        else:
            assert set(layer) == {"attn"} and layer["attn"]["k"].shape == (40, BS, 2, 16)
    with pytest.raises(ValueError, match="state_blocks"):
        init_paged_cache(model, 40, BS)
    cache = PagedKVCache(model, 3, block_size=BS)
    assert (cache.full_layers, cache.conv_layers, cache.linear_layers) == (2, 6, 0)
    assert cache.state_layers == 6 and cache.kinds == ("full", "conv")
    assert cache.state_bytes_per_block == 6 * 2 * 64 * 4 and "linear" not in cache.avals
    slot = cache.allocate()
    full, state = cache.tables()
    assert state.shape == (3, 1) and state[slot, 0] == cache.state_block(slot) == 0
    assert full.shape == (3, M // BS)
    cache.ensure_blocks(slot, 9)
    assert cache.bytes_live == 3 * cache.bytes_per_block + cache.state_bytes_per_block
    cache.free(slot)
    assert cache.state_live_blocks == 0 and cache.bytes_live == 0


def test_linear_and_conv_layers_share_the_state_table():
    """A model with both mixers: one block index a request in every layer
    that keeps a state, handed to the programs once a kind."""
    cfg = TransformerConfig(
        vocab_size=32, d_model=32, n_layers=3, n_heads=2, max_seq_len=32, use_flash=False,
        linear_heads=2, linear_key_dim=8, linear_value_dim=8,
        layers=(LayerSpec("linear"), LayerSpec("conv"), LayerSpec("full")))
    model = TransformerLM(cfg)
    assert cfg.cache_kinds == ("full", "linear", "conv")
    cache = PagedKVCache(model, 2, block_size=BS)
    assert cache.state_layers == 2 and cache.state_num_blocks == 2
    slot = cache.allocate()
    full, linear, conv = cache.tables()
    np.testing.assert_array_equal(linear, conv)
    assert linear[slot, 0] == cache.state_block(slot) != cache.state_invalid_block
    cache.free(slot)
    assert set(cache.tree["layers_0"]["linear_attn"]) == {"state", "conv"}
    assert set(cache.tree["layers_1"]["gated_conv"]) == {"tail"}
    per_block = (2 * 8 * 8 * 4 + 3 * 2 * 24 * 4) + 2 * 32 * 4
    assert cache.state_bytes_per_block == per_block
    variables = model.init(jax.random.PRNGKey(1), jnp.zeros((1, 4), jnp.int32))
    engine = ServeEngine(model, variables, slots=2, block_size=BS, prefill_chunk_tokens=8,
                         min_bucket=4)
    prompt = np.arange(13, dtype=np.int32) % 32
    rid = engine.submit(prompt, 5)
    tokens = engine.run(max_steps=100)[rid].tokens
    out = generate(model, variables, jnp.asarray(prompt)[None], 5)
    assert np.asarray(out)[0].tolist() == tokens


# --- (v) head size 64 in the paged kernels --------------------------------------

def test_a_pool_of_64_wide_heads_holds_two_a_row():
    assert [pool_head_pack(kv, dh) for kv, dh in
            ((8, 64), (2, 64), (1, 64), (3, 64), (8, 128), (8, 32), (20, 64), (32, 64))] == \
        [2, 2, 1, 1, 1, 1, 1, 2]
    assert pool_kv_shape(8, 64) == (4, 128) and pool_kv_shape(8, 128) == (8, 128)
    assert pool_kv_shape(30, 128) == (32, 128) and pool_kv_shape(2, 16) == (2, 16)


def _operands(seed, B, L, H, KV, Dh, dtype):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)
    return f(B, L, H, Dh), f(B, L, KV, Dh), f(B, L, KV, Dh)


def _dense(q, k, v, scale, lengths):
    """Grouped attention of q (B, L, H, Dh) at positions lengths[b] + i over
    keys k, v (B, M, KV, Dh), in float32."""
    B, L, H, Dh = q.shape
    KV = k.shape[2]
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    qg = q.reshape(B, L, KV, H // KV, Dh)
    s = jnp.einsum("blkrd,bmkd->bkrlm", qg, k) * scale
    pos = lengths[:, None] + jnp.arange(L)[None]
    mask = jnp.arange(k.shape[1])[None, None, :] <= pos[:, :, None]
    s = jnp.where(mask[:, None, None], s, -1e30)
    return jnp.einsum("bkrlm,bmkd->blkrd", jax.nn.softmax(s, axis=-1), v).reshape(B, L, H, Dh)


@pytest.mark.parametrize("Dh", [64, 128])
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_both_kernels_take_head_size_64_and_agree_with_the_dense_form(Dh, dtype, atol):
    """`paged_kernel` answers "decode" and "chunk" for the pool of a model
    of 64-wide heads as for one of 128-wide heads, and each kernel
    (interpreted here) agrees with `gather_paged_kv` + the dense form on
    the same operands: at 64 through the packed pool, two heads a row, at
    128 through the pool as it always was."""
    B, H, KV, bs, nb, nblk = 3, 8, 4, 16, 4, 16
    held, width = pool_kv_shape(KV, Dh)
    pack = width // Dh
    assert (held, width, pack) == ((2, 128, 2) if Dh == 64 else (4, 128, 1))
    rng = np.random.default_rng(7)
    pool_k = jnp.asarray(rng.normal(size=(nblk, bs, held, width)), dtype)
    pool_v = jnp.asarray(rng.normal(size=(nblk, bs, held, width)), dtype)
    tables = jnp.asarray(rng.permutation(nblk)[:B * nb].reshape(B, nb), jnp.int32)
    scale = Dh ** -0.5
    kf, vf = gather_paged_kv(pool_k, pool_v, tables, out_dtype=dtype)  # (B, nb * bs, held, width)
    keys = lambda a: a.reshape(B, nb * bs, KV, Dh)  # the model's own heads
    for L, lengths in ((1, jnp.asarray([5, 37, 63], jnp.int32)),
                       (16, jnp.asarray([0, 16, 48], jnp.int32))):
        kernel = paged_kernel(L, pool_k, tables)
        assert kernel == ("decode" if L == 1 else "chunk")
        q, _, _ = _operands(L, B, L, H, KV, Dh, dtype)
        qp = q
        if pack > 1:
            qp, _, _ = pack_pool_heads(q, jnp.zeros((B, L, KV, Dh), dtype),
                                       jnp.zeros((B, L, KV, Dh), dtype), pack)
        if L == 1:
            o = paged_decode_attention(qp[:, 0], pool_k, pool_v, tables, lengths, scale,
                                       interpret=True)[:, None]
        else:
            o = paged_chunk_attention(qp, pool_k, pool_v, tables, lengths, scale,
                                      interpret=True)
        o = o.reshape(B, L, -1)
        if pack > 1:
            o = unpack_pool_heads(o, KV, pack, Dh)
        want = _dense(q, keys(kf), keys(vf), scale, lengths)
        np.testing.assert_allclose(
            np.asarray(o, np.float32).reshape(B, L, H, Dh), np.asarray(want), rtol=0, atol=atol)


def test_packing_lays_a_query_against_its_own_head_alone():
    """q head h of KV head g = h // rep sits in part g % 2 of its row, zeros
    in the other; k and v are the same bytes in the same order; unpacking
    keeps each head's own part."""
    q, k, v = _operands(1, 2, 3, 8, 4, 64, jnp.float32)
    qp, kp, vp = pack_pool_heads(q, k, v, 2)
    assert qp.shape == (2, 3, 8, 128) and kp.shape == vp.shape == (2, 3, 2, 128)
    np.testing.assert_array_equal(np.asarray(kp).reshape(k.shape), np.asarray(k))
    for h in range(8):
        part = (h // 2) % 2
        np.testing.assert_array_equal(np.asarray(qp[:, :, h, 64 * part:64 * part + 64]),
                                      np.asarray(q[:, :, h]))
        assert not np.asarray(qp[:, :, h, 64 * (1 - part):64 * (2 - part)]).any()
    o = jnp.arange(2 * 3 * 8 * 128, dtype=jnp.float32).reshape(2, 3, 8 * 128)
    got = unpack_pool_heads(o, 4, 2, 64).reshape(2, 3, 8, 64)
    for h in range(8):
        part = (h // 2) % 2
        np.testing.assert_array_equal(
            np.asarray(got[:, :, h]),
            np.asarray(o.reshape(2, 3, 8, 128)[:, :, h, 64 * part:64 * part + 64]))


def test_an_engine_at_head_size_64_runs_both_kernels_and_gives_generate_s_tokens():
    """`layer_paths` says decode_kernel and chunk_kernel at head size 64,
    the pool is held as (blocks, 16, KV / 2, 128), and the served tokens are
    `generate()`'s, through prompts that end inside a bucket."""
    kinds = ["conv", "full", "conv", "full"]
    for dtype in (jnp.float32, jnp.bfloat16):
        cfg = TransformerConfig(
            vocab_size=97, d_model=256, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=128,
            max_seq_len=256, layers=tuple(LayerSpec(k) for k in kinds), rope_pairs="halves",
            qk_head_norm=True, tie_embeddings=True, use_flash=False, dtype=dtype)
        model = TransformerLM(cfg)
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
        engine = ServeEngine(model, variables, slots=3, block_size=16,
                             prefill_chunk_tokens=32, min_bucket=16)
        assert engine.cache.avals["full"]["k"].shape == (3 * 16, 16, 1, 128)
        assert engine.cache.bytes_per_block == 2 * 2 * 16 * 2 * 64 * jnp.dtype(dtype).itemsize
        snap = engine.metrics.snapshot()
        assert snap["decode"]["layer_paths"] == {"full": [2, "decode_kernel"],
                                                 "conv": [2, "conv_step"]}
        assert snap["prefill"]["layer_paths"] == {"full": [2, "chunk_kernel"],
                                                  "conv": [2, "conv_chunk"]}
        prompts = {"a": np.arange(70) * 7 % 97, "b": np.arange(21) * 5 % 97}
        for rid, prompt in prompts.items():
            engine.submit(prompt.astype(np.int32), 6, rid=rid)
        done = engine.run(max_steps=200)
        for rid, prompt in prompts.items():
            out = generate(model, variables, jnp.asarray(prompt, jnp.int32)[None], 6)
            assert np.asarray(out)[0].tolist() == done[rid].tokens, (rid, dtype)
