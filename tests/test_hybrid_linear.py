"""A model that mixes linear-attention (Gated DeltaNet) layers 3:1 with full
attention on the serve path, at a small size on the CPU: a recurrent state
block a request beside paged K/V, the chunked scan of a prefill chunk and
the recurrence of a decode step. The program's model is built by
`bench_matrix/glue/hybrid_linear.py` from a configuration in the published
file's own keys, and compared with `bench_matrix/reference/hybrid_linear.py`
(one `lax.scan` over tokens, no chunking) on seeded weights in float32: the
test of the layer's equations."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_matrix import correctness, modelglue
from bench_matrix.glue import hybrid_linear as glue
from bench_matrix.reference import hybrid_linear as reference
from pytorch_distributed_example_tpu.models.generate import generate, init_cache
from pytorch_distributed_example_tpu.models.transformer import (
    LINEAR_CHUNK,
    LayerSpec,
    TransformerConfig,
    TransformerLM,
    _delta_step,
    gated_delta_chunked,
)
from pytorch_distributed_example_tpu.serve import ServeEngine
from pytorch_distributed_example_tpu.serve.cache import PagedKVCache, init_paged_cache

ROOT = Path(__file__).resolve().parents[1]
PUBLISHED = json.loads(
    (ROOT / "bench_matrix" / "configs" / "olmo-hybrid-7b-d16.json").read_text())
BS, M = 4, 160
assert LINEAR_CHUNK == 64  # the lengths below are chosen around it
# the published file cut to a toy: the 3:1 pattern over two periods, a key
# width that is not the value width, no width of the model's
SMALL = dict(
    PUBLISHED, hidden_size=48, num_attention_heads=3, num_key_value_heads=3,
    intermediate_size=64, vocab_size=128, num_hidden_layers=8,
    layer_types=PUBLISHED["layer_types"][:8], linear_num_key_heads=3,
    linear_num_value_heads=3, linear_key_head_dim=8, linear_value_head_dim=12,
    dtype={"weights": "float32", "activations": "float32", "kv_cache": "float32",
           "recurrent_state": "float32"},
)
LIMITS = {"max_rel": 1e-4, "rms_rel": 1e-4}
LOOSE = {"max_rel": 1e-3, "rms_rel": 1e-3}
LINEAR = [i for i, kind in enumerate(SMALL["layer_types"]) if kind == "linear_attention"]


def build(**changed):
    """The toy through the glue."""
    return modelglue.build_model(dict(SMALL, **changed), M, remat=False)


def tame(variables):
    """Decay rates of 0.1 to 1 in place of the drawn ones (up to 16). A head
    that forgets everything at a token gives an output near 0 there, whose
    gated RMSNorm multiplies float32 rounding by a thousand: the two sides
    then differ by 1e-3 where they agree to 1e-5 everywhere else. The ends
    of alpha and beta are tested on the scan itself, below."""
    p = jax.tree_util.tree_map(lambda a: a, variables["params"])
    for name, layer in p.items():
        if "linear_attn" in layer:
            lin = layer["linear_attn"]
            lin["A_log"] = jnp.log(jnp.linspace(0.1, 1.0, lin["A_log"].shape[0]))
    return {"params": p}


@pytest.fixture(scope="module")
def small():
    model = build()
    return model, tame(modelglue.make_variables(model, SMALL, seed=11))


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
    tokens = tokens_of(150, seed)  # two sub-chunks of the scan and a third in part
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    out = correctness.compare(got, reference_logits(variables, tokens, 150), LIMITS)
    assert out["ok"], out


def test_the_sub_chunk_of_the_scan_does_not_show():
    """64 tokens a sub-chunk (the model's `LINEAR_CHUNK`), 16, 4, and 1
    (the recurrence in all but name) give one result, over a length that
    none but 1 divides."""
    q, k, v, state = _operands(5, L=150)
    rng = np.random.default_rng(6)
    g = jnp.asarray(-rng.uniform(0, 2, q.shape[:3]), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, q.shape[:3]), jnp.float32)
    want, want_state = gated_delta_chunked(q, k, v, g, beta, state, LINEAR_CHUNK)
    for chunk in (16, 4, 1):
        got, got_state = gated_delta_chunked(q, k, v, g, beta, state, chunk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-5)
        np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state), rtol=0,
                                   atol=2e-5)


def test_the_pattern_is_what_the_configuration_says(small):
    model, variables = small
    cfg = model.cfg
    assert cfg.linear_layers == (0, 1, 2, 4, 5, 6) and cfg.cache_kinds == ("full", "linear")
    assert cfg.window_layers == (False,) * 8 and cfg.sparse_layers == ()
    assert cfg.post_norm and cfg.qk_norm and cfg.linear_neg_eigval
    assert cfg.layer(3).rope.rotary_fraction == 0.0  # rope_theta: null rotates nothing
    p = variables["params"]
    assert "attn" not in p["layers_0"] and "linear_attn" not in p["layers_3"]
    lin = p["layers_0"]["linear_attn"]
    assert lin["q_proj"]["kernel"].shape == (48, 3 * 8)
    assert lin["v_proj"]["kernel"].shape == lin["g_proj"]["kernel"].shape == (48, 3 * 12)
    assert lin["o_proj"]["kernel"].shape == (3 * 12, 48)
    assert lin["conv"].shape == (4, 3 * (8 + 8 + 12)) and lin["norm"].shape == (12,)
    assert lin["A_log"].shape == lin["dt_bias"].shape == (3,)
    assert p["layers_3"]["attn"]["q_norm"]["scale"].shape == (48,)
    n = sum(a.size for a in jax.tree_util.tree_leaves(p))
    assert n == glue.param_count(SMALL)


def _reference_with(variables, tokens, change, **kw):
    """The reference's logits with `change` applied to every linear layer's
    weight dict."""
    emb, layers, norm, w_out = glue.reference_parts(variables)
    layers = [change(dict(w)) if "conv" in w else w for w in layers]
    return np.asarray(reference.logits(tokens, emb, layers, norm, w_out, SMALL,
                                       last=len(tokens), **kw))


def _newest_tap_only(w):
    w["conv"] = w["conv"].at[:-1].set(0.0)
    return w


def _nothing_forgotten(w):  # A_log very negative: alpha = 1
    w["A_log"] = w["A_log"] * 0.0 - 30.0
    return w


MECHANISMS = {
    "conv": dict(change=_newest_tap_only),
    "decay": dict(change=_nothing_forgotten),
    "beta_2x": dict(config=dict(SMALL, linear_allow_neg_eigval=False)),
    "l2_norm": dict(patched=("unit", lambda a: a)),
    "rope": dict(config=dict(SMALL, rope_parameters={"rope_theta": 500000})),
    "qk_norm": dict(model=dict(qk_norm=False)),
    "post_norm": dict(model=dict(post_norm=False)),
}


@pytest.mark.parametrize("what", sorted(MECHANISMS))
def test_the_comparison_sees_each_mechanism(small, what, monkeypatch):
    """With one term changed on one side alone the logits no longer agree:
    the conv's older taps, the decay, the 2 on beta, the L2 norm of q and
    k, a rotary embedding (on the reference's side); the q/k norm of the
    full layers, where the block's norms stand (on the program's)."""
    model, variables = small
    tokens = tokens_of(40, 9)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    want = reference_logits(variables, tokens, 40)
    case = MECHANISMS[what]
    if "change" in case:
        want = _reference_with(variables, tokens, case["change"])
    elif "config" in case:
        want = reference_logits(variables, tokens, 40, config=case["config"])
    elif "patched" in case:
        # a function of the reference replaced; its `layer` is compiled once
        # a setting, so it runs uncompiled here and meets the replacement
        monkeypatch.setattr(reference, *case["patched"])
        monkeypatch.setattr(reference, "layer", reference.layer.__wrapped__)
        want = reference_logits(variables, tokens, 40)
    else:
        other = TransformerLM(dataclasses.replace(model.cfg, **case["model"]))
        p = variables["params"]
        if what == "qk_norm":  # the other model has no such scales
            p = dict(p)
            for i in (3, 7):
                attn = {k: v for k, v in p[f"layers_{i}"]["attn"].items() if "norm" not in k}
                p[f"layers_{i}"] = dict(p[f"layers_{i}"], attn=attn)
        got = other.apply({"params": p}, jnp.asarray(tokens)[None])[0]
    assert not correctness.compare(got, want, LOOSE)["ok"]


def test_the_output_gate_is_in_the_comparison(small):
    """A gate of zero silences a linear layer on both sides alike, so the
    gate is shown the other way round: program and reference agree on
    weights whose gate projection was scaled, and that result is not the
    ungated one."""
    model, variables = small
    tokens = tokens_of(40, 9)
    p = jax.tree_util.tree_map(lambda a: a, variables["params"])
    for i in LINEAR:
        lin = p[f"layers_{i}"]["linear_attn"]
        lin["g_proj"] = {"kernel": -2.0 * lin["g_proj"]["kernel"]}
    scaled = {"params": p}
    got = model.apply(scaled, jnp.asarray(tokens)[None])[0]
    # a gate twice as steep takes one position's float32 rounding to 1.4e-4
    # of the logit range (rms 2.7e-5) at 64 tokens a sub-chunk
    near = dict(LIMITS, max_rel=3e-4)
    assert correctness.compare(got, reference_logits(scaled, tokens, 40), near)["ok"]
    assert not correctness.compare(got, reference_logits(variables, tokens, 40), LOOSE)["ok"]


def test_a_theta_given_rotates_halves_on_both_sides():
    """`assumed` (3)'s other reading: with a number for rope_theta the glue
    and the reference both rotate, and still agree."""
    config = dict(SMALL, rope_parameters={"rope_theta": 500000})
    model = build(**{"rope_parameters": config["rope_parameters"]})
    variables = tame(modelglue.make_variables(model, config, seed=3))
    tokens = tokens_of(30, 2)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    assert correctness.compare(got, reference_logits(variables, tokens, 30, config), LIMITS)["ok"]


def test_a_state_kept_in_bfloat16_fails_the_comparison(small):
    """What the cell's limits are set against: the reference's own logits
    with its recurrent state rounded to bfloat16 after every token."""
    model, variables = small
    tokens = tokens_of(120, 4)
    want = reference_logits(variables, tokens, 60)
    low = reference_logits(variables, tokens, 60, state_dtype=jnp.bfloat16)
    assert not correctness.compare(low, want, LOOSE)["ok"]


# --- (ii) the chunked scan against the recurrence ---------------------------

def _operands(seed, L=37, B=2, H=3, dk=8, dv=12):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(B, L, H, dk))) * dk ** -0.5
    k = unit(rng.normal(size=(B, L, H, dk)))
    v = rng.normal(size=(B, L, H, dv))
    state = rng.normal(size=(B, H, dk, dv))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, state)]


def _sequential(q, k, v, g, beta, state):
    outs = []
    for t in range(q.shape[1]):
        o, state = _delta_step(q[:, t], k[:, t], v[:, t], jnp.exp(g[:, t]), beta[:, t], state)
        outs.append(o)
    return jnp.stack(outs, axis=1), state


REGIMES = {
    # log(alpha) and beta: near their ends, and mixed
    "alpha_near_0": (lambda r, s: -r.uniform(20, 60, s), lambda r, s: r.uniform(0, 2, s)),
    "alpha_near_1": (lambda r, s: -r.uniform(0, 1e-4, s), lambda r, s: r.uniform(0, 2, s)),
    "beta_near_2": (lambda r, s: -r.uniform(0, 0.1, s), lambda r, s: r.uniform(1.99, 2, s)),
    "beta_near_0": (lambda r, s: -r.uniform(0, 3, s), lambda r, s: r.uniform(0, 1e-3, s)),
    "mixed": (lambda r, s: -r.exponential(1.0, s) * r.integers(0, 2, s) * 30,
              lambda r, s: r.uniform(0, 2, s)),
}


@pytest.mark.parametrize("chunk", [4, 64])
@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_the_chunked_scan_is_the_recurrence_to_float32_rounding(regime, chunk):
    q, k, v, state = _operands(7)
    rng = np.random.default_rng(8)
    make_g, make_beta = REGIMES[regime]
    g = jnp.asarray(make_g(rng, q.shape[:3]), jnp.float32)
    beta = jnp.asarray(make_beta(rng, q.shape[:3]), jnp.float32)
    want, want_state = _sequential(q, k, v, g, beta, state)
    got, got_state = gated_delta_chunked(q, k, v, g, beta, state, chunk)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 2e-5 * scale
    assert float(jnp.abs(got_state - want_state).max()) <= 2e-5 * float(jnp.abs(want_state).max())


def test_a_run_of_equal_keys_with_beta_2_stays_exact():
    """Sixty-four times the same key at beta = 2 and alpha = 1: the
    triangular system's powers cancel by thirty orders of magnitude; the
    blockwise inverse does not form them."""
    q, k, v, state = _operands(3, L=64, B=1)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g = jnp.zeros(q.shape[:3], jnp.float32)
    beta = jnp.full(q.shape[:3], 2.0, jnp.float32)
    want, want_state = _sequential(q, k, v, g, beta, state)
    got, got_state = gated_delta_chunked(q, k, v, g, beta, state, 64)
    assert np.isfinite(np.asarray(got)).all()
    assert float(jnp.abs(got - want).max()) <= 1e-4 * float(jnp.abs(want).max())
    assert float(jnp.abs(got_state - want_state).max()) <= 1e-4 * float(jnp.abs(want_state).max())


def test_positions_with_alpha_1_and_beta_0_leave_the_state_alone():
    q, k, v, state = _operands(5, L=24)
    rng = np.random.default_rng(6)
    g = jnp.asarray(-rng.uniform(0, 2, q.shape[:3]), jnp.float32).at[:, 17:].set(0.0)
    beta = jnp.asarray(rng.uniform(0, 2, q.shape[:3]), jnp.float32).at[:, 17:].set(0.0)
    _, padded = gated_delta_chunked(q, k, v, g, beta, state, 8)
    _, short = gated_delta_chunked(q[:, :17], k[:, :17], v[:, :17], g[:, :17], beta[:, :17],
                                   state, 8)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(short), rtol=0, atol=1e-6)


# --- (ii b) the decode kernel against the same update in jax.numpy -----------

def _pool_case(seed, nblk=6, B=6, H=4, dk=16, dv=64):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return dict(
        pool=f(nblk, H, dk, dv), q=f(B, H, dk), k=f(B, H, dk), v=f(B, H, dv),
        alpha=jnp.asarray(rng.uniform(0, 1, (B, H)), jnp.float32),
        beta=jnp.asarray(rng.uniform(0, 2, (B, H)), jnp.float32))


def _plain_step(pool, block, fresh, q, k, v, alpha, beta):
    """Gather, `_delta_step`, scatter: what a pool the kernel refuses gets."""
    state = jnp.where(fresh[:, None, None, None], 0.0,
                      jnp.take(pool, block, axis=0, mode="clip"))
    o, state = _delta_step(q, k, v, alpha, beta, state)
    return o, pool.at[block].set(state, mode="drop")


TABLES = {
    # each row's state block (6: none) and whether it starts from zero
    "every_row_live": ([3, 4, 0, 2, 5, 1], [0] * 6),
    "parked_rows_between_live_ones": ([3, 6, 0, 6, 5, 6], [0] * 6),
    "the_first_row_parked": ([6, 6, 2, 1, 6, 4], [0] * 6),
    "a_row_at_position_0": ([1, 6, 4, 0, 6, 6], [0, 0, 1, 0, 1, 0]),
    "one_live_row": ([6, 6, 6, 6, 2, 6], [0] * 6),
    "no_live_row": ([6] * 6, [0] * 6),
}


@pytest.mark.parametrize("case", sorted(TABLES))
def test_the_decode_kernel_is_the_plain_update(case):
    """Interpreted on the CPU: live rows' blocks updated in place, a block no
    live row holds bit for bit what it was, a parked row's output zero."""
    from pytorch_distributed_example_tpu.ops.delta_recurrence import (
        delta_kernel_ok, paged_delta_step)

    ops = _pool_case(3)
    block = jnp.asarray(TABLES[case][0], jnp.int32)
    fresh = jnp.asarray(TABLES[case][1], bool)
    assert delta_kernel_ok(ops["pool"])
    args = (ops["pool"], block, fresh, ops["q"], ops["k"], ops["v"], ops["alpha"], ops["beta"])
    o, pool = paged_delta_step(*args)
    want_o, want_pool = _plain_step(*args)
    live = np.asarray(block) < 6
    np.testing.assert_allclose(np.asarray(pool), np.asarray(want_pool), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live], rtol=1e-5, atol=1e-4)
    assert not np.asarray(o)[~live].any()
    untouched = sorted(set(range(6)) - set(np.asarray(block)[live].tolist()))
    assert np.array_equal(np.asarray(pool)[untouched], np.asarray(ops["pool"])[untouched])


def test_the_kernel_takes_the_cell_s_pool_and_not_the_toys():
    from pytorch_distributed_example_tpu.ops.delta_recurrence import delta_kernel_ok

    sd = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype)
    assert delta_kernel_ok(sd(32, 30, 96, 192))  # the published widths
    assert not delta_kernel_ok(sd(3, 3, 8, 12))  # this file's toy
    assert not delta_kernel_ok(sd(32, 30, 96, 192, dtype=jnp.bfloat16))
    assert not delta_kernel_ok(sd(32, 30, 100, 192))


def test_a_model_at_widths_the_kernel_takes_serves_the_reference_s_tokens():
    """dk 16 and dv 64: the engine's decode step runs the kernel
    (interpreted here), its prefill chunks the chunked scan over the same
    state blocks, parked lanes between live rows."""
    config = dict(SMALL, linear_key_head_dim=16, linear_value_head_dim=64,
                  num_hidden_layers=4, layer_types=SMALL["layer_types"][:4])
    model = modelglue.build_model(config, M, remat=False)
    variables = tame(modelglue.make_variables(model, config, seed=5))
    requests = [("a", tokens_of(13, 51), 6), ("b", tokens_of(5, 52), 2), ("c", tokens_of(9, 53), 7)]
    engine, _, _ = serve(model, variables, requests)
    snap = engine.metrics.snapshot()
    assert snap["decode"]["layer_paths"]["linear"] == [3, "recurrence_kernel"]
    assert snap["prefill"]["layer_paths"]["linear"] == [3, "chunk_scan"]
    for rid, prompt, n in requests:
        tokens = engine.completions[rid].tokens
        full = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
        want = reference_logits(variables, full, n, config=config)
        assert correctness.chosen_gap(want, tokens) <= 1e-4, rid


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
    for i in engine.cfg.linear_layers:
        leaves = engine.cache.tree[f"layers_{i}"]["linear_attn"]
        for name in ("state", "conv"):
            leaves[name] = leaves[name].at[free].set(1e3)


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
        assert len(mine) >= 5 and mine[-1][3] > 0  # state carried; padding
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


def test_a_served_chunk_of_two_sub_chunks_hands_its_state_on(small):
    """A prefill chunk of 128 tokens is two sub-chunks of the scan (the
    serve cells' buckets are two to eight): the state moves inside the
    chunk, then through the row's block to a padded second chunk and on to
    the decode steps."""
    model, variables = small
    prompt = tokens_of(150, 61)
    engine, chunks, _ = serve(model, variables, [("w", prompt, 6)], slots=2, chunk=128)
    assert [(s, len(t), pad) for s, t, _, pad in chunks] == [(0, 128, 0), (128, 22, 10)]
    want = reference_logits(variables, prompt, 150)
    for start, t, lg, _ in chunks:
        out = correctness.compare(lg[:len(t)], want[start:start + len(t)], LIMITS)
        assert out["ok"], (start, out)
    tokens = engine.completions["w"].tokens
    full = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    assert correctness.chosen_gap(reference_logits(variables, full, 6), tokens) <= 1e-4


def test_a_parked_lane_stood_between_two_live_rows(served):
    """The traffic above did what it was made for: at some step a free slot
    lay between two decoding ones, and requests took over blocks that
    others had held (poisoned in between)."""
    live = served["live"]
    assert max(live) == 3 and min(live) >= 0 and live[-1] <= 1
    cache = served["engine"].cache
    assert cache.state_live_blocks == 0 and sorted(cache._state_free) == [0, 1, 2]
    assert (cache.state_table == cache.state_invalid_block).all()
    snap = served["engine"].metrics.snapshot()
    assert snap["cache_pool"]["state_blocks_live"] in (0, 1)
    assert snap["decode"]["layer_paths"]["linear"] == [6, "recurrence"]
    assert snap["prefill"]["layer_paths"]["linear"] == [6, "chunk_scan"]
    assert snap["decode"]["layer_paths"]["full"][0] == 2
    # at the toy's widths no layer's mixer takes a kernel
    assert snap["prefill"]["kernel_share"] == 0.0 and snap["decode"]["kernel_share"] == 0.0


def test_generate_gives_the_served_tokens(small, served):
    model, variables = small
    for rid, prompt, n in REQUESTS[:2]:
        out = generate(model, variables, jnp.asarray(prompt)[None], n)
        assert np.asarray(out)[0].tolist() == served["done"][rid].tokens


def test_the_generate_cache_is_the_module_s_own(small):
    model, variables = small
    made = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 4), jnp.int32), decode=True)["cache"]
    built = init_cache(model, 2)
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), made) == \
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), built)
    assert set(built["layers_0"]) == {"linear_attn"} and set(built["layers_3"]) == {"attn"}


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


def test_padding_that_updated_the_state_would_fail(small):
    """The engine with its chunk padding written as token 0 (as a model
    without linear or sparse layers gets it): the padded last chunk moves
    the state and the decoded tokens leave the reference."""
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


# --- (iv) the cache manager ---------------------------------------------------

def test_a_linear_layer_allocates_no_kv_pool(small):
    model, _ = small
    cfg = model.cfg
    tree = init_paged_cache(model, 40, BS, state_blocks=3)
    nbytes = lambda t: sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(t))
    kv_layer = 2 * 40 * BS * 3 * 16 * 4  # K and V, 3 KV heads of 16, float32
    state_layer = 3 * (3 * 8 * 12 * 4 + 3 * 3 * (8 + 8 + 12) * 4)
    for i in range(8):
        layer = tree[f"layers_{i}"]
        if i in LINEAR:
            assert set(layer) == {"linear_attn"} and set(layer["linear_attn"]) == {"state", "conv"}
            assert layer["linear_attn"]["state"].shape == (3, 3, 8, 12)
            assert layer["linear_attn"]["state"].dtype == jnp.float32
            assert layer["linear_attn"]["conv"].shape == (3, 3, 3 * 28)
            assert nbytes(layer) == state_layer
        else:
            assert set(layer) == {"attn"} and nbytes(layer) == kv_layer
    assert nbytes(tree) == 2 * kv_layer + 6 * state_layer
    cache = PagedKVCache(model, 3, num_blocks=40, block_size=BS)
    assert (cache.full_layers, cache.window_layers, cache.linear_layers) == (2, 0, 6)
    assert cache.bytes_per_block * 40 == 2 * kv_layer
    assert cache.state_bytes_per_block * 3 == 6 * state_layer
    assert cfg.cache_kinds == cache.kinds == ("full", "linear")
    with pytest.raises(ValueError, match="state_blocks"):
        init_paged_cache(model, 40, BS)


def test_a_request_holds_one_state_block_from_admission_to_retirement(small):
    model, _ = small
    cache = PagedKVCache(model, 3, num_blocks=40, block_size=BS)
    assert cache.state_num_blocks == 3 and cache.state_live_blocks == 0
    a, b = cache.allocate(), cache.allocate()
    assert cache.state_live_blocks == 2 and cache.state_block(a) != cache.state_block(b)
    held = cache.state_block(a)
    cache.ensure_blocks(a, 30, 0)
    cache.ensure_blocks(a, 70, 31)
    assert cache.state_block(a) == held  # whatever its length
    full, state = cache.tables()
    assert full.shape == (3, M // BS) and state.shape == (3, 1)
    assert state[2, 0] == cache.state_invalid_block == 3
    _, parked = cache.tables(parked=[a])
    assert parked[a, 0] == 3 and parked[b, 0] == cache.state_block(b)
    assert cache.state_block(a) == held  # a copy was handed over
    _, row = cache.tables(slice(b, b + 1))
    assert row.shape == (1, 1) and row[0, 0] == cache.state_block(b)
    live = cache.bytes_live
    assert live == cache.live_blocks * cache.bytes_per_block + 2 * cache.state_bytes_per_block
    cache.free(a)
    assert cache.state_live_blocks == 1 and cache.state_block(a) == 3
    c = cache.allocate()
    assert cache.state_block(c) not in (3, cache.state_block(b))


def test_a_model_without_linear_layers_gets_the_tables_it_had():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=2, n_heads=2, max_seq_len=32,
                            use_flash=False)
    cache = PagedKVCache(TransformerLM(cfg), 2, block_size=4)
    assert cache.kinds == ("full",) and cache.linear_layers == 0
    assert cache.state_num_blocks == 0 and cache.state_bytes_per_block == 0
    assert isinstance(cache.tables(), np.ndarray)
    patterned = dataclasses.replace(cfg, layers=(LayerSpec(), LayerSpec()))
    assert isinstance(PagedKVCache(TransformerLM(patterned), 2, block_size=4).tables(), np.ndarray)


@pytest.mark.parametrize("heads", [10, 20])
def test_a_pool_of_whole_tiles_serves_a_model_of_ten_kv_heads(heads):
    """Ten KV heads are held as sixteen (`ops.paged_attention.pool_kv_heads`):
    q, k and v ride in with zero heads behind them, group by group, and
    the served tokens are `generate()`'s, whose cache holds ten."""
    from pytorch_distributed_example_tpu.ops.paged_attention import pool_kv_heads

    assert [pool_kv_heads(n) for n in (1, 3, 8, 10, 16, 30, 32)] == [1, 3, 8, 16, 16, 32, 32]
    cfg = TransformerConfig(vocab_size=64, d_model=80, n_layers=2, n_heads=heads, n_kv_heads=10,
                            d_ff=64, max_seq_len=64, use_flash=False, dtype=jnp.float32)
    model = TransformerLM(cfg)
    variables = model.init(jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))
    engine = ServeEngine(model, variables, slots=2, block_size=BS, prefill_chunk_tokens=8,
                         min_bucket=4)
    assert engine.cache.tree["layers_0"]["attn"]["k"].shape[2] == engine.cache.avals["full"]["k"].shape[2] == 16
    prompts = {"a": tokens_of(13, 71) % 64, "b": tokens_of(6, 72) % 64}
    for rid, prompt in prompts.items():
        engine.submit(prompt, 7, rid=rid)
    done = engine.run(max_steps=200)
    for rid, prompt in prompts.items():
        out = generate(model, variables, jnp.asarray(prompt)[None], 7)
        assert np.asarray(out)[0].tolist() == done[rid].tokens, rid


REFUSED = {
    "prefix_cache": dict(prefix_cache=True),
    "kv_quant": dict(kv_quant=True),
    "mesh": dict(mesh=object()),
    "role": dict(role="prefill"),
    "precompiled": dict(precompiled={"anything": 1}),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_what_a_linear_model_cannot_be_served_with_is_refused(small, what):
    model, variables = small
    with pytest.raises(ValueError, match="a model with linear layers cannot be served with"):
        ServeEngine(model, variables, slots=2, **REFUSED[what])


def test_a_pattern_that_contradicts_itself_is_refused():
    base = dict(vocab_size=64, d_model=32, n_layers=1, n_heads=2, max_seq_len=32)
    with pytest.raises(ValueError, match="linear_heads"):
        TransformerConfig(layers=(LayerSpec("linear"),), **base)
    with pytest.raises(ValueError, match="linear_heads"):
        TransformerConfig(layers=(LayerSpec("linear"),), linear_heads=2, linear_key_dim=4,
                          linear_value_dim=4, linear_conv=0, **base)
    with pytest.raises(ValueError, match="positions and a pre-built state pool"):
        cfg = TransformerConfig(layers=(LayerSpec("linear"),), linear_heads=2,
                                linear_key_dim=4, linear_value_dim=4, **base)
        model = TransformerLM(cfg)
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
        model.apply(variables, jnp.zeros((1, 4), jnp.int32), decode=True,
                    positions=jnp.zeros((1,), jnp.int32),
                    block_tables=jnp.zeros((1, 1), jnp.int32), mutable=["cache"])
