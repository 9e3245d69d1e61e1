"""A model that mixes linear-attention layers whose decay is a vector a head
(Kimi Delta Attention: low-rank gates, a sigmoid output gate) 3:1 with NoPE
grouped-query attention under an elementwise output gate, every layer's MLP
sparse with a shared expert and an eighth of the routed experts held, on the
serve path, at a small size on the CPU: a recurrent state block a request
beside paged K/V, the chunked scan of a prefill chunk and the recurrence of a
decode step, both with a decay a state ROW. The program's model is built by
`bench_matrix/glue/kda_moe.py` from a configuration in the published file's
own keys, and compared with `bench_matrix/reference/kda_moe.py` (one
`lax.scan` over tokens, no chunking) on seeded weights in float32: the test
of the layer's equations."""

import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_matrix import correctness, modelglue
from bench_matrix.glue import kda_moe as glue
from bench_matrix.reference import kda_moe as reference
from pytorch_distributed_example_tpu.models.generate import generate, init_cache
from pytorch_distributed_example_tpu.models.transformer import (
    LINEAR_BLOCK,
    LINEAR_CHUNK,
    LayerSpec,
    TransformerConfig,
    TransformerLM,
    _delta_step,
    gated_delta_chunked,
)
from pytorch_distributed_example_tpu.ops.delta_recurrence import (
    delta_kernel_ok,
    paged_delta_step,
)
from pytorch_distributed_example_tpu.serve import ServeEngine
from pytorch_distributed_example_tpu.serve.cache import PagedKVCache

ROOT = Path(__file__).resolve().parents[1]
PUBLISHED = json.loads(
    (ROOT / "bench_matrix" / "configs" / "solar-open2-250b-d4-e40.json").read_text())
BS, M = 4, 160
assert (LINEAR_CHUNK, LINEAR_BLOCK) == (64, 16)  # the lengths below are chosen around them
F32 = dict(PUBLISHED["dtype"], weights="float32", activations="float32",
           kv_cache="float32", conv_tail="float32")
# the published file cut to a toy: two periods of the pattern (softmax,
# linear x 3), 4 of 32 experts held (chip 0 of 8), top 4; no width of the
# model's
WHOLE = dict(
    PUBLISHED["published"], hidden_size=48, num_attention_heads=4, num_key_value_heads=2,
    head_dim=12, intermediate_size=64, moe_intermediate_size=24, vocab_size=128,
    num_hidden_layers=8, gqa_layers=[0, 4], n_routed_experts=32, num_experts_per_tok=4,
    linear_attn_config=dict(PUBLISHED["linear_attn_config"], num_heads=3, head_dim=8))
SMALL = dict(PUBLISHED, **dict(WHOLE, n_routed_experts=4), published=WHOLE, dtype=F32,
             model=dict(PUBLISHED["model"], check=None))
LIMITS = {"max_rel": 1e-4, "rms_rel": 1e-4}
LOOSE = {"max_rel": 1e-3, "rms_rel": 1e-3}
LINEAR = [i for i in range(8) if i not in SMALL["gqa_layers"]]


def build(**changed):
    """The toy through the glue."""
    return modelglue.build_model(dict(SMALL, **changed), M, remat=False)


def tame(variables):
    """Decay rates of 0.1 to 1 in place of the drawn ones (up to 16). A head
    that forgets everything at a token gives an output near 0 there, whose
    gated RMSNorm multiplies float32 rounding by a thousand (tests/
    test_hybrid_linear.py::tame). The ends of the gate are tested on the scan
    itself, below."""
    p = jax.tree_util.tree_map(lambda a: a, variables["params"])
    for layer in p.values():
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


def test_the_pattern_is_what_the_configuration_says(small):
    model, variables = small
    cfg = model.cfg
    assert cfg.linear_layers == (1, 2, 3, 5, 6, 7) and cfg.cache_kinds == ("full", "linear")
    assert cfg.sparse_layers == tuple(range(8)) and cfg.experts_held == (0, 4)
    assert (cfg.linear_decay, cfg.linear_gate_rank, cfg.linear_neg_eigval) == ("channel", 8, True)
    assert cfg.attn_out_gate and not cfg.attn_gate and not cfg.qk_norm and not cfg.post_norm
    assert cfg.layer(0).rope.rotary_fraction == 0.0  # use_rope false rotates nothing
    assert cfg.sparse_score == "sigmoid" and cfg.sparse_choice_bias and cfg.shared_d_ff == 24
    p = variables["params"]
    assert "attn" not in p["layers_1"] and "linear_attn" not in p["layers_4"]
    lin = p["layers_1"]["linear_attn"]
    assert lin["q_proj"]["kernel"].shape == lin["v_proj"]["kernel"].shape == (48, 24)
    assert lin["f_proj_a"]["kernel"].shape == lin["g_proj_a"]["kernel"].shape == (48, 8)
    assert lin["f_proj_b"]["kernel"].shape == lin["g_proj_b"]["kernel"].shape == (8, 24)
    assert lin["A_log"].shape == (3,) and lin["dt_bias"].shape == (3, 8)
    assert lin["b_proj"]["kernel"].shape == (48, 3) and lin["conv"].shape == (4, 72)
    assert "a_proj" not in lin and "g_proj" not in lin
    assert p["layers_0"]["attn"]["out_gate"]["kernel"].shape == (48, 48)
    assert p["layers_0"]["mlp"]["router"].shape == (48, 32)
    assert p["layers_0"]["mlp"]["experts_gate"].shape == (4, 48, 24)
    assert p["layers_0"]["mlp"]["shared_expert"]["up_proj"]["kernel"].shape == (48, 24)
    assert sum(a.size for a in jax.tree_util.tree_leaves(p)) == glue.param_count(SMALL)


def test_a_full_projection_is_refused_and_the_rank_belongs_to_the_vector_form():
    """`kda_use_full_proj: true` (one matrix for the decay and one for the
    gate) is carried by no configuration: the glue refuses it, and the
    program's vector form needs a rank as its scalar form refuses one."""
    from bench_matrix import spec

    with pytest.raises(spec.SpecError, match="kda_use_full_proj"):
        build(kda_use_full_proj=True)
    model = build()
    with pytest.raises(ValueError, match="linear_gate_rank"):
        dataclasses.replace(model.cfg, linear_gate_rank=0)
    with pytest.raises(ValueError, match="linear_gate_rank"):
        dataclasses.replace(model.cfg, linear_decay="head", linear_gate_rank=8)
    with pytest.raises(ValueError, match="linear_decay"):
        dataclasses.replace(model.cfg, linear_decay="row")


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


def _no_dt_bias(w):
    w["dt_bias"] = w["dt_bias"] * 0.0
    return w


MECHANISMS = {
    "conv": dict(change=_newest_tap_only),
    "decay": dict(change=_nothing_forgotten),
    "dt_bias": dict(change=_no_dt_bias),
    "scalar_decay": dict(fault="scalar_decay"),
    "beta_2x": dict(fault="beta_01"),
    "elementwise_gate": dict(fault="head_gate"),
    "shared_expert": dict(fault="no_shared"),
    "l2_norm": dict(patched=("unit", lambda a: a)),
}


@pytest.mark.parametrize("what", sorted(MECHANISMS))
def test_the_comparison_sees_each_mechanism(small, what, monkeypatch):
    """With one term changed on the reference's side alone the logits no
    longer agree: the conv's older taps, the decay, its bias a channel, the
    decay as a vector (`test_a_scalar_decay_would_fail` is this case by its
    name), the 2 on beta, the gate's elementwise form, the shared expert, the
    L2 norm of q and k."""
    model, variables = small
    tokens = tokens_of(40, 9)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    case = MECHANISMS[what]
    if "change" in case:
        want = _reference_with(variables, tokens, case["change"])
    elif "fault" in case:
        want = reference_logits(variables, tokens, 40, fault=case["fault"])
    else:
        monkeypatch.setattr(reference, *case["patched"])
        want = reference_logits(variables, tokens, 40)
    assert not correctness.compare(got, want, LOOSE)["ok"]


def test_a_scalar_decay_would_fail(small):
    """A mixer that decayed a head's state by ONE alpha (the mean of the
    channels' log-decays: what the linear kind's first form computes) leaves
    the reference by far more than rounding."""
    model, variables = small
    tokens = tokens_of(60, 4)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    assert correctness.compare(got, reference_logits(variables, tokens, 60), LIMITS)["ok"]
    out = correctness.compare(
        got, reference_logits(variables, tokens, 60, fault="scalar_decay"), LOOSE)
    assert not out["ok"] and out["rms_rel"] > 1e-2


def test_a_state_kept_in_bfloat16_fails_the_comparison(small):
    """What the cell's limits are set against: the reference's own logits
    with its recurrent state rounded to bfloat16 after every token."""
    model, variables = small
    tokens = tokens_of(120, 4)
    want = reference_logits(variables, tokens, 60)
    low = reference_logits(variables, tokens, 60, state_dtype=jnp.bfloat16)
    assert not correctness.compare(low, want, LOOSE)["ok"]


def test_what_the_rule_is_computed_from_in_bfloat16_fails_the_comparison(small):
    """The lower-precision control the cell's `rms_rel` decides with room
    (planted in the PROGRAM on the chip: PERF.md section 6, PR 47): q, k, v,
    the decay and beta rounded to bfloat16 at the rule's door, state and
    sums float32. The rule multiplies such a rounding, so it shows where a
    state rounded at a call's end does not."""
    model, variables = small
    tokens = tokens_of(120, 4)
    want = reference_logits(variables, tokens, 60)
    low = reference_logits(variables, tokens, 60, rule_dtype=jnp.bfloat16)
    out = correctness.compare(low, want, LOOSE)
    assert not out["ok"] and out["rms_rel"] > 2e-3


@pytest.mark.parametrize("tie_margin", [None, 0.02, 0.005])
def test_a_tie_margin_refuses_the_told_choices_whose_deficit_passes_it(tie_margin):
    """`sparse_ffn` told another router's experts: a token's `deficit` is how
    far below this router's own last chosen biased score the lowest of its
    told experts lies; the told choice is taken up to `tie_margin` and this
    router's own beyond it. Token 0 is told its own choice in another
    order, token 1 its ninth expert for its eighth, token 2 its last-ranked
    for its eighth, token 3 nothing."""
    rng = np.random.default_rng(5)
    D, F, E, K, T = 32, 16, 24, 8, 4
    f = lambda *shape, s=1.0: jnp.asarray(rng.normal(size=shape) * s, jnp.float32)
    n = f(T, D)
    w = {"router": f(D, E, s=D ** -0.5), "router_bias": f(E, s=0.05),
         "experts_gate": f(E, D, F, s=D ** -0.5), "experts_up": f(E, D, F, s=D ** -0.5),
         "experts_down": f(E, F, D, s=F ** -0.5), "shared_gate": f(D, F, s=D ** -0.5),
         "shared_up": f(D, F, s=D ** -0.5), "shared_down": f(F, D, s=F ** -0.5)}
    ffn = lambda **kw: reference.sparse_ffn(n, w, top_k=K, scale=1.0, first_expert=0, **kw)
    _, own = ffn()
    biased = np.asarray(jax.nn.sigmoid(n @ w["router"]) + w["router_bias"])
    ranked = np.argsort(-biased, axis=-1)
    np.testing.assert_array_equal(np.sort(ranked[:, :K]), np.sort(np.asarray(own["chosen"])))
    told = ranked[:, :K].copy()
    told[0] = told[0][::-1]
    told[1, -1], told[2, -1], told[3] = ranked[1, K], ranked[2, -1], -1
    _, said = ffn(routing=jnp.asarray(told, jnp.int32), tie_margin=tie_margin)
    eighth = biased[np.arange(T), ranked[:, K - 1]]
    want = [0.0, eighth[1] - biased[1, ranked[1, K]], eighth[2] - biased[2, ranked[2, -1]], 0.0]
    np.testing.assert_allclose(np.asarray(said["deficit"]), want, atol=1e-6)
    assert 0 < want[1] < want[2] and 0.005 < want[2]
    np.testing.assert_array_equal(np.asarray(said["differs"]), [False, True, True, False])
    refused = [tie_margin is not None and d > tie_margin for d in want]
    np.testing.assert_array_equal(np.asarray(said["refused"]), refused)
    for t in range(T):
        used = ranked[t, :K] if refused[t] or t == 3 else told[t]
        np.testing.assert_array_equal(np.sort(np.asarray(said["chosen"][t])), np.sort(used))


def test_the_elementwise_and_per_head_gates_differ_and_refuse_each_other(small):
    """`attn_out_gate` is a projection of the output's full width
    (`out_gate`), `attn_gate` one value a head (`head_gate`): two models,
    two results, and a configuration that asks for both is refused."""
    model, variables = small
    cfg = model.cfg
    with pytest.raises(ValueError, match="two gates of one output"):
        dataclasses.replace(cfg, attn_gate=True)
    per_head = TransformerLM(dataclasses.replace(cfg, attn_out_gate=False, attn_gate=True))
    p = jax.tree_util.tree_map(lambda a: a, variables["params"])
    for i in SMALL["gqa_layers"]:
        attn = dict(p[f"layers_{i}"]["attn"])
        gate = attn.pop("out_gate")["kernel"]  # (48, 4 x 12): the mean logit a head
        attn["head_gate"] = {"kernel": gate.reshape(48, 4, 12).mean(axis=-1)}
        p[f"layers_{i}"] = dict(p[f"layers_{i}"], attn=attn)
    tokens = tokens_of(40, 9)
    got = per_head.apply({"params": p}, jnp.asarray(tokens)[None])[0]
    # the per-head program is the reference's planted per-head gate, and not
    # the elementwise one
    same = reference_logits(variables, tokens, 40, fault="head_gate")
    assert correctness.compare(got, same, LIMITS)["ok"]
    assert not correctness.compare(got, reference_logits(variables, tokens, 40), LOOSE)["ok"]
    ungated = TransformerLM(dataclasses.replace(cfg, attn_out_gate=False))
    shapes = jax.eval_shape(ungated.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert set(shapes["params"]["layers_0"]["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}


# --- (ii) the share of the experts --------------------------------------------

def test_the_eight_shares_parts_add_up_to_the_uncut_layer():
    """The guide's share test: the sparse layer's output for experts 0-3,
    4-7, ... 28-31 (the router at its full 32 outputs, top 4, the weights
    normalised over all four chosen whether held or not), added, with the
    shared expert that every chip computes alike counted ONCE, is the uncut
    reference's whole layer; in the program's `dropless_moe` and in the
    reference's `sparse_ffn` alike."""
    from pytorch_distributed_example_tpu.parallel.expert_parallel import dropless_moe

    rng = np.random.default_rng(3)
    D, F, E, K, T, chips = 48, 24, 32, 4, 50, 8
    f = lambda *shape, s=1.0: jnp.asarray(rng.normal(size=shape) * s, jnp.float32)
    n = f(T, D)
    w = {"router": f(D, E, s=D ** -0.5), "router_bias": f(E, s=0.05),
         "experts_gate": f(E, D, F, s=D ** -0.5), "experts_up": f(E, D, F, s=D ** -0.5),
         "experts_down": f(E, F, D, s=F ** -0.5), "shared_gate": f(D, F, s=D ** -0.5),
         "shared_up": f(D, F, s=D ** -0.5), "shared_down": f(F, D, s=F ** -0.5)}
    part = lambda first, count: {k: (v[first:first + count] if k.startswith("experts") else v)
                                 for k, v in w.items()}
    ffn = lambda weights, first, **kw: reference.sparse_ffn(
        n, weights, top_k=K, scale=1.0, first_expert=first, **kw)
    whole, routed = ffn(w, 0)
    shared = reference.swiglu(n, w["shared_gate"], w["shared_up"], w["shared_down"])
    held = E // chips
    parts = [ffn(part(first, held), first) for first in range(0, E, held)]
    # each share's result holds the shared expert: take it off all but once
    total = sum(y for y, _ in parts) - (chips - 1) * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=0, atol=2e-5)
    for _, said in parts:  # every chip routes alike, over all 32
        np.testing.assert_array_equal(np.asarray(said["chosen"]), np.asarray(routed["chosen"]))
    assert float(jnp.abs(shared).max()) > 1e-2
    assert all(float(jnp.abs(y - shared).max()) > 1e-3 for y, _ in parts)
    system = []
    for first in range(0, E, held):
        mine = part(first, held)
        y, stats, chosen = dropless_moe(
            n, w["router"], mine["experts_gate"], mine["experts_up"], mine["experts_down"],
            n_experts=E, top_k=K, scale=1.0, first_expert=first, score="sigmoid",
            choice_bias=w["router_bias"])
        system.append(y)
        np.testing.assert_array_equal(
            np.sort(np.asarray(chosen), axis=-1), np.sort(np.asarray(routed["chosen"]), axis=-1))
        np.testing.assert_allclose(  # the routed part alone: the share's, less the shared one
            np.asarray(y), np.asarray(parts[first // held][0] - shared), rtol=0, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(sum(system) + shared), np.asarray(whole), rtol=0, atol=4e-5)


def test_a_model_that_holds_every_expert_agrees_with_the_uncut_reference():
    """Through the glue: the toy with all 32 experts held."""
    whole_cfg = dict(SMALL, n_routed_experts=32)
    model = modelglue.build_model(whole_cfg, M, remat=False)
    assert model.cfg.experts_held is None
    variables = tame(modelglue.make_variables(model, whole_cfg, seed=5))
    tokens = tokens_of(24, 8)
    want = reference_logits(variables, tokens, 24, config=whole_cfg)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    assert correctness.compare(got, want, LIMITS)["ok"]
    assert glue.param_count(whole_cfg) == sum(
        a.size for a in jax.tree_util.tree_leaves(variables["params"]))


# --- (iii) the chunked scan against the recurrence ---------------------------

def _operands(seed, L=37, B=2, H=3, dk=8, dv=8):
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
    # log(alpha) a channel and beta: near their ends, and mixed
    "alpha_near_0": (lambda r, s: -r.uniform(20, 60, s), lambda r, s: r.uniform(0, 2, s)),
    "alpha_near_1": (lambda r, s: -r.uniform(0, 1e-4, s), lambda r, s: r.uniform(0, 2, s)),
    "beta_near_2": (lambda r, s: -r.uniform(0, 0.1, s), lambda r, s: r.uniform(1.99, 2, s)),
    "beta_near_0": (lambda r, s: -r.uniform(0, 3, s), lambda r, s: r.uniform(0, 1e-3, s)),
    "channels_far_apart": (lambda r, s: -r.exponential(1.0, s) * r.integers(0, 2, s) * 30,
                           lambda r, s: r.uniform(0, 2, s)),
}


@pytest.mark.parametrize("chunk", [4, 16, 64])
@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_the_chunked_scan_is_the_recurrence_to_float32_rounding(regime, chunk):
    """A sub-chunk of 4 is one block of the secondary chunking, 16 exactly
    one, 64 four of them: the products inside a block and between blocks."""
    q, k, v, state = _operands(7, L=150)
    rng = np.random.default_rng(8)
    make_g, make_beta = REGIMES[regime]
    g = jnp.asarray(make_g(rng, q.shape), jnp.float32)  # (B, L, H, dk)
    beta = jnp.asarray(make_beta(rng, q.shape[:3]), jnp.float32)
    want, want_state = _sequential(q, k, v, g, beta, state)
    got, got_state = gated_delta_chunked(q, k, v, g, beta, state, chunk)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 2e-5 * scale
    assert float(jnp.abs(got_state - want_state).max()) <= 2e-5 * float(jnp.abs(want_state).max())


@pytest.mark.parametrize("step", [0.1, 1.0, 40.0])
def test_the_chunked_scan_holds_at_the_extreme_gates(step):
    """The top of the gate's initial range: exp(A_log) = 16 times a step of
    0.1 is log(alpha) = -1.6 a token, -102 over a 64-token sub-chunk, past
    where float32 ends (e^88): the factorised product (k e^G)(k e^-G)^T
    overflows there, the blocked one stays finite and equal to the
    recurrence. A seeded gate projection takes the step to 1 and beyond
    (-16 a token, -640 over a block's 16 tokens at 40): still finite and
    equal. Channel 0 of every head does not decay at all, so the sums mix the
    two ends."""
    q, k, v, state = _operands(11, L=128, dk=16, dv=16)
    a_log = math.log(16.0)
    g = jnp.full(q.shape, -math.exp(a_log) * step, jnp.float32).at[..., 0].set(0.0)
    beta = jnp.asarray(np.random.default_rng(2).uniform(0, 2, q.shape[:3]), jnp.float32)
    assert float(jnp.cumsum(g, axis=1)[:, 63].min()) < -88.0
    want, want_state = _sequential(q, k, v, g, beta, state)
    got, got_state = gated_delta_chunked(q, k, v, g, beta, state, LINEAR_CHUNK)
    assert np.isfinite(np.asarray(got)).all() and np.isfinite(np.asarray(got_state)).all()
    assert float(jnp.abs(got - want).max()) <= 2e-5 * float(jnp.abs(want).max())
    assert float(jnp.abs(got_state - want_state).max()) <= 2e-5 * float(jnp.abs(want_state).max())
    # and the factorised form does overflow: what the blocks are for
    G = jnp.cumsum(g[:, :64], axis=1)
    assert not np.isfinite(np.asarray(k[:, :64] * jnp.exp(-G))).all()


def test_a_decay_constant_along_the_channels_is_the_scalar_form():
    """One alpha repeated over a head's channels is the linear kind's first
    form: the two branches of the scan, the two of `_delta_step`."""
    q, k, v, state = _operands(5, L=150)
    rng = np.random.default_rng(6)
    g = jnp.asarray(-rng.uniform(0, 2, q.shape[:3]), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, q.shape[:3]), jnp.float32)
    wide = jnp.broadcast_to(g[..., None], q.shape)
    want, want_state = gated_delta_chunked(q, k, v, g, beta, state, LINEAR_CHUNK)
    got, got_state = gated_delta_chunked(q, k, v, wide, beta, state, LINEAR_CHUNK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state), rtol=0, atol=2e-5)
    o, s = _delta_step(q[:, 0], k[:, 0], v[:, 0], jnp.exp(g[:, 0]), beta[:, 0], state)
    o2, s2 = _delta_step(q[:, 0], k[:, 0], v[:, 0], jnp.exp(wide[:, 0]), beta[:, 0], state)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o), rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s), rtol=0, atol=1e-6)


def test_positions_with_alpha_1_and_beta_0_leave_the_state_alone():
    q, k, v, state = _operands(5, L=24)
    rng = np.random.default_rng(6)
    g = jnp.asarray(-rng.uniform(0, 2, q.shape), jnp.float32).at[:, 17:].set(0.0)
    beta = jnp.asarray(rng.uniform(0, 2, q.shape[:3]), jnp.float32).at[:, 17:].set(0.0)
    _, padded = gated_delta_chunked(q, k, v, g, beta, state, 8)
    _, short = gated_delta_chunked(q[:, :17], k[:, :17], v[:, :17], g[:, :17], beta[:, :17],
                                   state, 8)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(short), rtol=0, atol=1e-6)


# --- (iii b) the decode kernel against the same update in jax.numpy -----------

def _pool_case(seed, nblk=6, B=6, H=4, dk=16, dv=64):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return dict(
        pool=f(nblk, H, dk, dv), q=f(B, H, dk), k=f(B, H, dk), v=f(B, H, dv),
        alpha=jnp.asarray(rng.uniform(0, 1, (B, H, dk)), jnp.float32),
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
    "a_row_at_position_0": ([1, 6, 4, 0, 6, 6], [0, 0, 1, 0, 1, 0]),
    "no_live_row": ([6] * 6, [0] * 6),
}


@pytest.mark.parametrize("case", sorted(TABLES))
def test_the_decode_kernel_with_a_vector_decay_is_the_plain_update(case):
    """Interpreted on the CPU: the decay rides down the sublanes, one alpha a
    state row; live rows' blocks updated in place, a block no live row holds
    bit for bit what it was, a parked row's output zero."""
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


def test_a_decay_constant_along_dk_is_today_s_scalar_kernel():
    """The vector kernel with one alpha repeated down a head's rows gives
    what the scalar kernel gives for that alpha (to float32 rounding: alpha
    S^T k against S^T (alpha k))."""
    ops = _pool_case(4)
    block, fresh = jnp.asarray([3, 6, 0, 2, 5, 1], jnp.int32), jnp.asarray([0, 0, 1, 0, 0, 0], bool)
    scalar = ops["alpha"][..., 0]
    wide = jnp.broadcast_to(scalar[..., None], ops["alpha"].shape)
    rest = (ops["q"], ops["k"], ops["v"])
    o1, pool1 = paged_delta_step(ops["pool"], block, fresh, *rest, scalar, ops["beta"])
    o2, pool2 = paged_delta_step(ops["pool"], block, fresh, *rest, wide, ops["beta"])
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(pool2), np.asarray(pool1), rtol=1e-5, atol=1e-5)


def test_the_kernel_takes_the_cell_s_pool_and_not_the_toys():
    sd = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype)
    assert delta_kernel_ok(sd(16, 64, 128, 128))  # the published widths, 16 slots
    assert not delta_kernel_ok(sd(3, 3, 8, 8))  # this file's toy
    assert not delta_kernel_ok(sd(16, 64, 128, 128, dtype=jnp.bfloat16))


# --- (iv) the serve path ----------------------------------------------------

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
    # neighbour has retired, so a parked lane stands between the two live
    # ones; two that take over freed slots and their poisoned blocks
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


def test_several_rows_were_live_and_blocks_changed_hands(served):
    """The traffic above did what it was made for, on the linear kind's own
    records: no sixth kind, the vector form named by `layer_paths`."""
    live = served["live"]
    assert max(live) == 3 and live[-1] <= 1
    cache = served["engine"].cache
    assert cache.kinds == ("full", "linear") and cache.linear_layers == 6
    assert cache.state_live_blocks == 0 and sorted(cache._state_free) == [0, 1, 2]
    assert cache.tree["layers_1"]["linear_attn"]["state"].shape == (3, 3, 8, 8)
    assert cache.tree["layers_1"]["linear_attn"]["state"].dtype == jnp.float32
    assert cache.tree["layers_1"]["linear_attn"]["conv"].shape == (3, 3, 72)
    snap = served["engine"].metrics.snapshot()
    assert snap["decode"]["layer_paths"]["linear"] == [6, "vector_recurrence"]
    assert snap["prefill"]["layer_paths"]["linear"] == [6, "vector_chunk_scan"]
    assert snap["decode"]["layer_paths"]["full"][0] == 2


def test_a_served_chunk_of_two_sub_chunks_hands_its_state_on(small):
    """A prefill chunk of 128 tokens is two sub-chunks of the scan, each of
    four blocks: the state moves inside the chunk, then through the row's
    block to a padded second chunk and on to the decode steps."""
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


def test_generate_gives_the_served_tokens(small, served):
    model, variables = small
    for rid, prompt, n in REQUESTS[:2]:
        out = generate(model, variables, jnp.asarray(prompt)[None], n)
        assert np.asarray(out)[0].tolist() == served["done"][rid].tokens


def test_generate_s_cache_gives_the_reference_s_logits(small):
    """`decode=True` without tables: the prompt in one call, then a token a
    call from the module's own state and conv tail."""
    model, variables = small
    tokens = tokens_of(30, 5)
    cache = init_cache(model, 1)
    made = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32), decode=True)["cache"]
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), made) == \
        jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), cache)
    assert set(cache["layers_1"]) == {"linear_attn"} and set(cache["layers_0"]) == {"attn"}
    rows = []
    for piece in (tokens[:21], *(tokens[i:i + 1] for i in range(21, 30))):
        logits, out = model.apply(
            {"params": variables["params"], "cache": cache}, jnp.asarray(piece)[None],
            decode=True, mutable=["cache"])
        cache = out["cache"]
        rows.append(np.asarray(logits[0]))
    out = correctness.compare(np.concatenate(rows), reference_logits(variables, tokens, 30), LIMITS)
    assert out["ok"], out


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
    full = np.concatenate([requests[1][1], np.asarray(alone["b"][:-1], np.int32)])
    assert correctness.chosen_gap(reference_logits(variables, full, 16), alone["b"]) <= 1e-4


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


@pytest.mark.parametrize("fault", ["stale_tail", "neighbour_tail", "neighbour_state"])
def test_a_wrong_hand_over_would_fail(small, served, fault):
    """The reference with a planted hand-over fault (the conv tail one token
    stale or another request's at every hand-over, the state block of one
    chunk earlier at the last one) is not what the engine served."""
    prompt = REQUESTS[0][1]
    tokens = served["done"]["long"].tokens
    full = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    sound = reference_logits(served["variables"], full, len(tokens))
    wrong = reference_logits(served["variables"], full, len(tokens), fault=fault, chunk=8,
                             prompt=len(prompt))
    assert correctness.chosen_gap(sound, tokens) <= 1e-4
    assert not correctness.compare(wrong, sound, LOOSE)["ok"]


def test_a_model_at_widths_the_kernel_takes_serves_the_reference_s_tokens():
    """Heads of 64 x 64: the engine's decode step runs the kernel with the
    vector decay (interpreted here), its prefill chunks the chunked scan over
    the same state blocks, parked lanes between live rows."""
    lin = dict(SMALL["linear_attn_config"], num_heads=2, head_dim=64)
    config = dict(SMALL, num_hidden_layers=4, linear_attn_config=lin,
                  published=dict(WHOLE, linear_attn_config=lin))
    model = modelglue.build_model(config, M, remat=False)
    variables = tame(modelglue.make_variables(model, config, seed=5))
    requests = [("a", tokens_of(13, 51), 6), ("b", tokens_of(5, 52), 2), ("c", tokens_of(9, 53), 7)]
    engine, _, _ = serve(model, variables, requests)
    snap = engine.metrics.snapshot()
    assert snap["decode"]["layer_paths"]["linear"] == [3, "vector_recurrence_kernel"]
    assert snap["prefill"]["layer_paths"]["linear"] == [3, "vector_chunk_scan"]
    for rid, prompt, n in requests:
        tokens = engine.completions[rid].tokens
        full = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
        want = reference_logits(variables, full, n, config=config)
        assert correctness.chosen_gap(want, tokens) <= 1e-4, rid


def test_the_state_block_is_the_linear_kind_s_own(small):
    """What a KDA layer keeps is what the linear kind keeps: no new record in
    `serve/kinds.py`, the same refusals, one state block a request."""
    from pytorch_distributed_example_tpu.serve.kinds import KINDS

    model, variables = small
    assert tuple(KINDS) == ("full", "window", "linear", "latent", "conv")
    cache = PagedKVCache(model, 3, num_blocks=40, block_size=BS)
    assert cache.state_num_blocks == 3 and cache.kinds == ("full", "linear")
    per_layer = 3 * 8 * 8 * 4 + 3 * 72 * 4
    assert cache.state_bytes_per_block == 6 * per_layer
    with pytest.raises(ValueError, match="a model with linear layers cannot be served with"):
        ServeEngine(model, variables, slots=2, prefix_cache=True)
    base = dict(vocab_size=64, d_model=32, n_layers=1, n_heads=2, max_seq_len=32)
    with pytest.raises(ValueError, match="linear_heads"):
        TransformerConfig(layers=(LayerSpec("linear"),), linear_decay="channel",
                          linear_gate_rank=4, **base)
