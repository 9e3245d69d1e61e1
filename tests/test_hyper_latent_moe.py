"""A model with four residual streams on the serve path, at a small size on
the CPU: Sinkhorn-normalised hyper-connections around multi-head latent
attention (YaRN on its rope, the factor on the softmax scale) and a
sigmoid-routed dropless MoE whose router chooses with a bias, latent blocks
under the prefix cache. The program's model is built by
`bench_matrix/glue/hyper_latent_moe.py` from a configuration in the
published file's own keys, and compared with
`bench_matrix/reference/hyper_latent_moe.py` (the NON-absorbed equations,
the Sinkhorn loop a Python loop) on seeded weights in float32."""

import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_matrix import correctness, modelglue
from bench_matrix.glue import hyper_latent_moe as glue
from bench_matrix.reference import hyper_latent_moe as reference
from pytorch_distributed_example_tpu.models.transformer import (
    Attention, Block, HyperConnection, LayerSpec, MLP, RMSNorm, RopeSpec, TransformerConfig,
    TransformerLM, rope_table,
)
from pytorch_distributed_example_tpu.ops import (
    latent_chunk_attention, latent_decode_attention, paged_attention, paged_kernel,
    pool_latent_width,
)
from pytorch_distributed_example_tpu.parallel.expert_parallel import dropless_moe
from pytorch_distributed_example_tpu.serve import ServeEngine

from test_latent_moe import (
    BS, M, _chunk_operands, _einsum_reference, _layer_reference, _pool_and_tables, _serve_by_hand,
)
from test_paged_attention import NBLK, SPAN, share_tables, work_list  # tables that share pages
from test_sparse_window import Probe  # keeps every prefill chunk's (start, tokens, logits)

ROOT = Path(__file__).resolve().parents[1]
PUBLISHED = json.loads(
    (ROOT / "bench_matrix" / "configs" / "xing4.0-29b-a4b-d8.json").read_text())
F32 = {"weights": "float32", "activations": "float32", "kv_cache": "float32"}


def toy(**sizes):
    """The published file cut to a toy: every mechanism, no width of the
    model's; YaRN stretches 32 original positions by 8, so the ramp crosses
    the toy's four frequencies and the tests' 60 positions pass the
    original length."""
    cfg = dict(PUBLISHED, **sizes, dtype=F32)
    cfg["published"] = dict(PUBLISHED["published"], n_routed_experts=sizes["n_routed_experts"])
    cfg["rope_scaling"] = dict(
        PUBLISHED["rope_scaling"], factor=8, original_max_position_embeddings=32)
    cfg["model"] = {k: v for k, v in PUBLISHED["model"].items() if k != "check"}
    return cfg


# one dense layer, then two sparse ones; rows of 40 values: no kernel takes them
SMALL = toy(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, vocab_size=128,
    num_hidden_layers=3, first_k_dense_replace=1, n_routed_experts=8,
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
    tokens = tokens_of(60, seed)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    out = correctness.compare(got, reference_logits(variables, tokens, 60), LIMITS)
    assert out["ok"], out


def test_the_pattern_is_what_the_configuration_says(small):
    model, variables = small
    cfg = model.cfg
    assert [s.attention for s in cfg.layers] == ["latent"] * 3
    assert [s.mlp for s in cfg.layers] == ["dense", "sparse", "sparse"]
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_clamp) == (
        4, 20, 1e-6, (-30.0, 30.0))
    assert (cfg.sparse_score, cfg.sparse_choice_bias, cfg.routed_scale) == ("sigmoid", True, 2)
    assert not cfg.sandwich_norm and cfg.experts_held is None
    rope = cfg.layers[0].rope
    assert rope.yarn == (8.0, 32, 32.0, 1.0, 1.0)
    assert rope.softmax_factor == pytest.approx((0.1 * np.log(8) + 1) ** 2)
    p = variables["params"]
    assert set(p["layers_1"]) == {"attn_norm", "mlp_norm", "latent_attn", "mlp",
                                  "hc_attn", "hc_mlp"}
    assert p["layers_1"]["hc_attn"]["phi"].shape == (4 * 64, 2 * 4 + 16)
    assert p["layers_1"]["hc_attn"]["alpha"].shape == (3,)
    assert p["layers_1"]["hc_mlp"]["bias"].shape == (24,)
    assert p["layers_1"]["mlp"]["router_bias"].shape == (8,)
    assert "router_bias" not in p["layers_0"]["mlp"]
    assert {k: v.shape for k, v in p["hc_out"].items()} == {
        "phi": (256, 4), "alpha": (1,), "bias": (4,)}


def test_the_published_scale_carries_mscale_squared():
    model = modelglue.build_model(PUBLISHED, 64, remat=False)
    rope = model.cfg.layers[0].rope
    assert rope.softmax_factor == pytest.approx(2.0048, abs=1e-4)
    assert rope.yarn == (64.0, 4096, 32.0, 1.0, 1.0)
    inv, on_tables, scale = reference.rope_numbers(PUBLISHED)
    assert on_tables == 1.0 and scale == pytest.approx(192 ** -0.5 * 2.0048, rel=1e-4)
    cos, sin = rope_table(rope, 64, 8)
    np.testing.assert_allclose(np.asarray(cos[1]), np.cos(np.asarray(inv)), rtol=1e-6)


@pytest.mark.parametrize("what", ["groups", "a_share", "softmax_router", "linear_rope"])
def test_the_glue_refuses_what_the_program_does_not_carry(what):
    from bench_matrix import spec

    cfg = {
        "groups": dict(SMALL, n_group=2),
        "a_share": dict(SMALL, n_routed_experts=4),
        "softmax_router": dict(SMALL, scoring_func="softmax"),
        "linear_rope": dict(SMALL, rope_scaling=dict(SMALL["rope_scaling"], type="linear")),
    }[what]
    with pytest.raises(spec.SpecError, match="is not carried"):
        modelglue.build_model(cfg, M, remat=False)


# --- (ii) the maps alone ------------------------------------------------------

@pytest.fixture(scope="module")
def maps_alone():
    cfg = TransformerConfig(
        d_model=64, n_layers=1, n_heads=4, norm_eps=1e-6, hc_mult=4,
        layers=(LayerSpec("full"),))
    hc = HyperConnection(cfg)
    # the streams lead: (n, B, L, C)
    X = jax.random.normal(jax.random.PRNGKey(1), (4, 3, 10, 64), jnp.float32) * 1.7
    params = hc.init(jax.random.PRNGKey(2), X, method=HyperConnection.pre)
    return cfg, hc, params, X


def test_the_maps_are_the_reference_s(maps_alone):
    cfg, hc, params, X = maps_alone
    u, h_post, h_res = hc.apply(params, X, method=HyperConnection.pre)
    p = params["params"]
    numbers = dict(n=4, iters=20, eps=1e-6, norm_eps=1e-6, clamp=(-30.0, 30.0))
    for b in range(3):
        Xb = jnp.moveaxis(X[:, b], 0, 1)  # the reference's (L, n, C)
        want_u, want_post, want_res = reference.maps(
            Xb, p["phi"], p["alpha"], p["bias"], **numbers)
        np.testing.assert_allclose(u[b], want_u, atol=2e-6)
        np.testing.assert_allclose(h_post[:, b].T, want_post, atol=1e-6)
        np.testing.assert_allclose(jnp.moveaxis(h_res[:, :, b], -1, 0), want_res, atol=1e-6)
        y = jnp.flip(want_u, axis=0)
        np.testing.assert_allclose(
            jnp.moveaxis(HyperConnection.post(X[:, b], y, h_post[:, b], h_res[:, :, b]), 0, 1),
            reference.write_back(jnp.array(Xb), y, want_post, want_res), atol=5e-6)


def test_h_res_is_doubly_stochastic_and_far_from_identity_and_uniform(maps_alone):
    _, hc, params, X = maps_alone
    _, h_post, h_res = hc.apply(params, X, method=HyperConnection.pre)
    np.testing.assert_allclose(h_res.sum(axis=0), 1.0, atol=1e-3)
    np.testing.assert_allclose(h_res.sum(axis=1), 1.0, atol=1e-3)
    assert float(h_res.min()) >= 0.0 and 0.0 < float(h_post.min()) < float(h_post.max()) < 2.0
    # seeded maps depend on the token: neither the identity nor 1/4
    assert float(jnp.abs(h_res - jnp.eye(4)[:, :, None, None]).mean()) > 0.1
    assert float(jnp.abs(h_res - 0.25).mean()) > 0.02
    assert float(h_res.std(axis=(2, 3)).mean()) > 0.02


def test_a_map_is_a_function_of_its_own_token_only(maps_alone):
    """What a chunk's padding or a step's parked row holds reaches no other
    row: to the bit."""
    _, hc, params, X = maps_alone
    other = X.at[:, 1, 4].set(1e3).at[:, 2].set(-7.0)
    a = hc.apply(params, X, method=HyperConnection.pre)
    b = hc.apply(params, other, method=HyperConnection.pre)
    keep = np.ones((3, 10), bool)
    keep[1, 4] = keep[2] = False
    y = jnp.ones((3, 10, 64))
    lefts = a + (HyperConnection.post(X, y, *a[1:]),)
    rights = b + (HyperConnection.post(other, y, *b[1:]),)
    # where the batch axis stands in u, h_post, h_res and the new streams
    for left, right, batch_axis in zip(lefts, rights, (0, 1, 2, 1)):
        left, right = (np.moveaxis(np.asarray(t), (batch_axis, batch_axis + 1), (0, 1))
                       for t in (left, right))
        assert np.array_equal(left[keep], right[keep])


def test_the_maps_are_float32_under_a_bfloat16_model(maps_alone):
    cfg, hc, params, X = maps_alone
    Xb = X.astype(jnp.bfloat16)
    u, h_post, h_res = hc.apply(params, Xb, method=HyperConnection.pre)
    assert (u.dtype, h_post.dtype, h_res.dtype) == (jnp.bfloat16, jnp.float32, jnp.float32)
    want = hc.apply(params, Xb.astype(jnp.float32), method=HyperConnection.pre)
    np.testing.assert_allclose(np.asarray(h_res), np.asarray(want[2]), atol=2e-6)
    assert HyperConnection.post(Xb, u, h_post, h_res).dtype == jnp.bfloat16


def test_one_stream_is_the_block_as_it_was():
    """`hc_mult` 1: x + attention(norm(x)), then x + mlp(norm(x)), to the
    bit, and no map among the parameters."""
    cfg = TransformerConfig(d_model=64, n_layers=1, n_heads=4, d_ff=96, norm_eps=1e-6,
                            use_flash=False, layers=(LayerSpec("full"),))
    spec = cfg.layer(0)
    block = Block(cfg, spec)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 64))
    cos, sin = rope_table(spec.rope, cfg.head_dim, 12)
    params = block.init(jax.random.PRNGKey(1), x, cos, sin)["params"]
    assert set(params) == {"attn_norm", "attn", "mlp_norm", "mlp"}
    norm = lambda name, h: RMSNorm(cfg.norm_eps).apply({"params": params[name]}, h)
    h = x + Attention(cfg, spec).apply({"params": params["attn"]}, norm("attn_norm", x), cos, sin)
    want = h + MLP(cfg).apply({"params": params["mlp"]}, norm("mlp_norm", h))
    got = block.apply({"params": params}, x, cos, sin)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_streams_belong_to_a_pattern():
    with pytest.raises(ValueError, match="hc_mult"):
        TransformerConfig(hc_mult=4)
    with pytest.raises(ValueError, match="hc_mult"):
        TransformerConfig(hc_mult=0)


# --- (iii) planted faults ----------------------------------------------------

@pytest.mark.parametrize("fault", reference.FAULTS + ("the_maps_in_bfloat16",))
def test_each_planted_fault_fails_the_comparison(small, fault):
    """The program against a reference with one thing wrong: the comparison
    that passes the sound pair at 1e-4 reads at least a hundred times that."""
    model, variables = small
    tokens = tokens_of(60, 11)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    kw = {"hc_dtype": jnp.bfloat16} if fault == "the_maps_in_bfloat16" else {"fault": fault}
    out = correctness.compare(got, reference_logits(variables, tokens, 60, **kw), LIMITS)
    assert not out["ok"] and out["rms_rel"] > 5e-3, out


def test_a_fault_that_is_not_one_is_refused(small):
    with pytest.raises(ValueError, match="is none of"):
        reference_logits(small[1], tokens_of(8), 8, fault="h_res_is_identity")


def test_a_latent_cache_in_float8_fails_the_comparison(small):
    model, variables = small
    tokens = tokens_of(60, 12)
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    want = reference_logits(variables, tokens, 60, kv_dtype=jnp.float8_e4m3fn)
    assert not correctness.compare(got, want, {"max_rel": 1e-2, "rms_rel": 1e-2})["ok"]


# --- (iv) the router's bias ---------------------------------------------------

def _moe_operands(T=48, D=64, F=16, E=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape) / np.sqrt(shape[-2]), jnp.float32)
    return (jnp.asarray(rng.normal(size=(T, D)), jnp.float32), f(D, E), f(E, D, F), f(E, D, F),
            f(E, F, D), jnp.asarray(rng.normal(size=(E,)) * 0.05, jnp.float32))


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_the_bias_moves_the_choice_and_no_weight(score):
    x, router, w_gate, w_up, w_down, beta = _moe_operands()
    kw = dict(n_experts=16, top_k=4, scale=2.0, score=score)
    plain_y, _, plain_e = dropless_moe(x, router, w_gate, w_up, w_down, **kw)
    y, stats, chosen = dropless_moe(x, router, w_gate, w_up, w_down, **kw, choice_bias=beta)
    logits = np.asarray(x @ router, np.float64)
    s = 1 / (1 + np.exp(-logits)) if score == "sigmoid" else (
        np.exp(logits) / np.exp(logits).sum(-1, keepdims=True))
    want_e = np.argsort(-(s + np.asarray(beta)), axis=-1)[:, :4]
    assert np.array_equal(np.sort(chosen, -1), np.sort(want_e, -1))
    moved = (np.sort(chosen, -1) != np.sort(plain_e, -1)).any(-1).mean()
    # a few per cent of the rows and more; beside softmax scores of 1/16 the
    # bias is large
    assert 0.02 < moved < (0.6 if score == "sigmoid" else 1.0)
    # the weights are the scores without the bias: rebuild the layer from them
    w = np.take_along_axis(s, want_e, -1)
    w = 2.0 * w / w.sum(-1, keepdims=True)
    out = np.zeros((48, 64))
    for t in range(48):
        for e, we in zip(want_e[t], w[t]):
            h = jax.nn.silu(x[t] @ w_gate[e]) * (x[t] @ w_up[e])
            out[t] += we * np.asarray(h @ w_down[e])
    np.testing.assert_allclose(np.asarray(y), out, atol=2e-4)
    assert int(stats[0]) == 48 * 4
    # a zero bias is the layer without one, to the bit
    zero_y, _, zero_e = dropless_moe(x, router, w_gate, w_up, w_down, **kw,
                                     choice_bias=jnp.zeros(16))
    assert np.array_equal(np.asarray(zero_y), np.asarray(plain_y))
    assert np.array_equal(np.asarray(zero_e), np.asarray(plain_e))


def test_the_model_s_counters_and_told_choices_see_the_biased_choice(small):
    model, variables = small
    tokens = tokens_of(40, 5)
    _, out = model.apply(variables, jnp.asarray(tokens)[None], mutable=["intermediates"])
    record = []
    emb, layers, norm, w_out = glue.reference_parts(variables)
    reference.logits(tokens, emb, layers, norm, w_out, SMALL, last=1, record=record)
    for routed in record:
        chosen = out["intermediates"][f"layers_{routed['layer']}"]["mlp"]["moe_chosen"][0][0]
        safe = np.asarray(routed["margin"]) > 1e-5
        assert safe.mean() > 0.9
        assert np.array_equal(np.sort(np.asarray(chosen), -1)[safe],
                              np.sort(np.asarray(routed["chosen"]), -1)[safe])
    unbiased = []
    reference.logits(tokens, emb, layers, norm, w_out, SMALL, last=1, record=unbiased,
                     fault="bias_not_in_the_choice")
    flipped = np.mean([
        (np.sort(np.asarray(a["chosen"]), -1) != np.sort(np.asarray(b["chosen"]), -1)).any(-1).mean()
        for a, b in zip(record[:1], unbiased[:1])])
    assert 0.01 < flipped < 0.6


# --- (v) the cached paths -----------------------------------------------------

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
    assert cache.slot_blocks(1) == [] and (cache.block_tables[1] == cache.invalid_block).all()


@pytest.mark.parametrize("fault", ["scale_without_mscale", "plain_rope"])
def test_the_cached_paths_carry_the_scaled_softmax_and_the_stretched_rope(wide, fault):
    """Both kernels are handed the layer's scale and rotate by YaRN's table:
    a reference without either leaves the decoded logits."""
    model, variables = wide
    served, _ = _serve_by_hand(model, variables, [tokens_of(37, 2)], steps=3, parked=())
    seq, logits = served[0]
    want = reference_logits(variables, seq[:-1], 4, WIDE, fault=fault)
    assert not correctness.compare(logits, want, {"max_rel": 1e-2, "rms_rel": 1e-2})["ok"]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5), (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("lengths", [(0, 15, 16), (255, 256, 257), (100, 31, 300)])
def test_the_latent_decode_kernel_at_32_heads_is_the_masked_einsum(lengths, dtype, tol):
    """32 heads and the published softmax scale (192^-0.5 x 2.0048), lengths
    on both sides of a page edge and of a key block's, a parked row."""
    scale = 192 ** -0.5 * 2.0048
    rng = np.random.default_rng(sum(lengths))
    pool, tables = _pool_and_tables(rng, lengths + (-1,), dtype=dtype)
    at = np.asarray(lengths + (tables.shape[1] * 16 - 1,), np.int32)
    q = jnp.asarray(rng.normal(size=(4, 32, 256)), dtype)
    got = latent_decode_attention(q, pool, jnp.asarray(tables), jnp.asarray(at), scale,
                                  rank=128, interpret=True)
    want = _einsum_reference(q[:, None], pool, tables, at[:, None], 128, scale)[:, 0]
    assert got.shape == (4, 32, 128) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got[:3], np.float32), np.asarray(want[:3], np.float32),
                               atol=tol)
    assert not np.asarray(got[3], np.float32).any()


# (shared pages, rows of the group), lengths, parked rows -> a row's shared
# blocks, every shared item's rows; ten rows of 32 heads over tables of 40 pages
# of 16 keys (2.5 compute blocks): one compiled kernel a dtype, the tables and
# lengths its operands
GROUPED = {
    # rows 0-7 behind one 512-key head: ONE stacked pass a block, the rows
    # staying stacked from the first block to the second
    "a_group_of_a_whole_pass": (
        [(32, range(8))], [600, 639, 530, 512, 513, 555, 620, 599, 77, 300], (),
        [2] * 8 + [0, 0], [list(range(8))] * 2),
    # all ten rows: a pass of eight and a pass of two a block
    "a_group_larger_than_a_pass": (
        [(32, range(10))], [600, 639, 530, 512, 513, 555, 620, 599, 527, 639], (),
        [2] * 10, [list(range(10))] * 2),
    "two_groups_of_different_shared_lengths": (
        [(32, (1, 3, 4)), (16, (0, 5))], [300, 600, 77, 520, 639, 511, 0, 15, 16, 255], (),
        [1, 2, 0, 2, 2, 1, 0, 0, 0, 0], [[0, 5], [1, 3, 4], [1, 3, 4]]),
    # 33 pages shared: two whole blocks are read once, the block the run
    # ends in is each row's own (its first page the same page in every row)
    "a_run_that_ends_inside_a_compute_block": (
        [(33, (0, 1, 2, 9))], [600, 639, 530, 40, 100, 256, 257, 300, 10, 575], (),
        [2, 2, 2, 0, 0, 0, 0, 0, 0, 2], [[0, 1, 2, 9]] * 2),
    # row 2's length is the head's last key: no item of its own, finished by
    # its last shared block; row 0 writes the first key of a page of its own
    "a_row_shared_but_for_the_token_it_writes": (
        [(32, (0, 1, 2))], [512, 602, 511, 40, 100, 256, 257, 300, 10, 575], (),
        [2, 2, 2] + [0] * 7, [[0, 1, 2]] * 2),
    # row 1 is parked inside the group's range, row 3 ends inside the second
    # block (its own, masked at its length) and shares only the first
    "a_parked_row_inside_a_group_s_range": (
        [(32, range(5))], [600, 639, 639, 356, 530, 40, 100, 256, 257, 300], (1,),
        [2, 0, 2, 1, 2] + [0] * 5, [[0, 2, 3, 4], [0, 2, 4]]),
    # four rows of 128 heads: a stacked pass is eight rows there too
    "128_heads": ([(32, (0, 1, 2))], [600, 639, 511, 40], (), [2, 2, 2, 0], [[0, 1, 2]] * 2),
}


@functools.lru_cache(maxsize=None)
def _grouped_programs(dtype, H):
    scale = 192 ** -0.5 * 2.0048
    kernel = jax.jit(lambda q, pool, tables, at: latent_decode_attention(
        q, pool, tables, at, scale, rank=128, interpret=True))
    reference = jax.jit(lambda q, pool, tables, at: _einsum_reference(
        q[:, None], pool, tables, at[:, None], 128, scale)[:, 0])
    rng = np.random.default_rng(H)
    pool = jnp.asarray(rng.normal(size=(NBLK, 16, 256)), dtype)
    return kernel, reference, pool, lambda B: jnp.asarray(rng.normal(size=(B, H, 256)), dtype)


def grouped_check(dtype, tol, H, groups, lengths, parked):
    """The kernel against the gather and the masked einsum on tables that
    share pages; the host's count against the device's list. Returns
    `work_list`'s (skip a row, the shared items, every item's rows)."""
    tables = share_tables(groups, lengths, seed=len(lengths), parked=parked)
    at = np.asarray(lengths, np.int32)
    at[list(parked)] = SPAN - 1
    kernel, reference, pool, q_of = _grouped_programs(dtype, H)
    q = q_of(len(lengths))
    got = np.asarray(kernel(q, pool, jnp.asarray(tables), jnp.asarray(at)), np.float32)
    want = np.asarray(reference(q, pool, jnp.asarray(tables), jnp.asarray(at)), np.float32)
    live = [r for r in range(len(lengths)) if r not in parked]
    assert np.isfinite(got).all() and not got[list(parked)].any()
    np.testing.assert_allclose(got[live], want[live], atol=tol)
    skip, items, members = work_list(tables, at.tolist())
    keys = paged_attention.shared_decode_keys(tables, at, NBLK, 16)
    assert keys == sum(skip) * 256 == sum(len(m) for m in members) * 256
    return skip, items, members


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5), (jnp.bfloat16, 4e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(GROUPED))
def test_the_latent_decode_kernel_reads_a_shared_block_once_a_group(case, dtype, tol):
    """A block that several rows' tables hold is copied once and meets the
    group's rows stacked, all their heads in one product
    (`_latent_stacked_rows`: eight rows at 32 heads and at 128); what is
    shared is `shared_runs`' to say, as for a K/V pool."""
    H = 128 if case == "128_heads" else 32
    assert paged_attention._latent_stacked_rows(H) == 8
    *tables, skip, members = GROUPED[case]
    assert grouped_check(dtype, tol, H, *tables)[::2] == (skip, members)


def _row_block_kernel(
    tables_ref, n_pages_ref, last_ref, item_row_ref, item_blk_ref, n_items_ref,
    q_ref, pool_hbm, o_ref, buf, sems, m_s, l_s, acc_s, *, scale, nb, P, rank,
):
    """The latent decode kernel as it was before it had a shared list (PR 33's
    text): every block of every row a (row, block) item."""
    from jax import lax
    from jax.experimental import pallas as pl

    H, T = q_ref.shape[1], P * pool_hbm.shape[1]
    n_items = n_items_ref[0]
    precision = paged_attention._precision(buf.dtype)
    col = lax.broadcasted_iota(jnp.int32, (H, T), 1)
    buf[...] = jnp.zeros_like(buf)
    o_ref[...] = jnp.zeros_like(o_ref)

    def page_copies(item, slot, fn):
        row = item_row_ref[item]
        first = item_blk_ref[item] * P
        have = jnp.minimum(n_pages_ref[row] - first, P)
        paged_attention._page_copies(
            fn, tables_ref, row * nb + first, have, (pool_hbm,), (buf,), sems, slot)

    @pl.when(n_items > 0)
    def _():
        page_copies(0, 0, lambda cp: cp.start())

    def body(item, carry):
        slot = item % 2

        @pl.when(item + 1 < n_items)
        def _():
            page_copies(item + 1, 1 - slot, lambda cp: cp.start())

        row, blk = item_row_ref[item], item_blk_ref[item]

        @pl.when(blk == 0)
        def _():
            m_s[...] = jnp.full_like(m_s, paged_attention.NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

        page_copies(item, slot, lambda cp: cp.wait())
        q, k = q_ref[row], buf[slot]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())), precision=precision,
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(col < last_ref[row] - blk * T + 1, s, paged_attention.NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = alpha * l_s[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = alpha * acc_s[...] + jnp.dot(
            p.astype(k.dtype), k[:, :rank], precision=precision,
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

        @pl.when((blk + 1) * P >= n_pages_ref[row])
        def _():
            o_ref[row] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)

        return carry

    lax.fori_loop(0, n_items, body, 0)


def _row_block_call(q, pool, tables, lengths, scale, rank):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (B, H, W), (nblk, bs, _), nb = q.shape, pool.shape, tables.shape[1]
    P = paged_attention._pages_per_block(bs, nb)
    scalars = paged_attention._work_list(tables, lengths, nblk, bs, P)
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_row_block_kernel, scale=scale, nb=nb, P=P, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(1,),
            in_specs=[vmem(), pl.BlockSpec(memory_space=pl.ANY)], out_specs=vmem(),
            scratch_shapes=[
                pltpu.VMEM((2, P * bs, W), pool.dtype), pltpu.SemaphoreType.DMA((1, 2)),
                pltpu.VMEM((H, 1), jnp.float32), pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, rank), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype), interpret=True,
    )(*scalars, q, pool)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_without_a_common_page_the_latent_kernel_is_the_kernel_it_was(dtype):
    """Tables in which no two rows hold one page: the shared list is empty,
    the (row, block) list is what `_work_list` gave without one, and the
    output is BIT-equal to the kernel that had none."""
    lengths = (2 * 256 + 37, 5, 256 + 3, SPAN - 1)
    rng = np.random.default_rng(8)
    pool, tables = _pool_and_tables(rng, lengths, nblk=NBLK, nb=40, dtype=dtype)
    q = jnp.asarray(rng.normal(size=(4, 32, 256)), dtype)
    at, tables = jnp.asarray(lengths, jnp.int32), jnp.asarray(tables)
    with_list = paged_attention._work_list(tables, at, NBLK, 16, 16, share=True)
    without = paged_attention._work_list(tables, at, NBLK, 16, 16)
    assert int(with_list[9][0]) == 0 and not np.asarray(with_list[6]).any()
    assert all(np.array_equal(a, b) for a, b in zip(with_list[:6], without))
    got = latent_decode_attention(q, pool, tables, at, 0.1, rank=128, interpret=True)
    was = _row_block_call(q, pool, tables, at, 0.1, 128)
    assert np.array_equal(np.asarray(got), np.asarray(was))
    assert np.abs(np.asarray(got, np.float32)).max() > 0.01


def test_the_latent_predicate_counts_the_shared_list_s_scalars():
    """The shared list rides in scalar memory beside the (row, block) list:
    32 rows of 4608 pages fit with it, 4800 fit only without (which is what
    the predicate counted before the kernel had one)."""
    pool = jax.ShapeDtypeStruct((16384, 16, 640), jnp.bfloat16)
    tables = lambda nb: jax.ShapeDtypeStruct((32, nb), jnp.int32)
    assert paged_kernel(1, pool, tables(4608), rank=512) == "latent_decode"
    assert paged_kernel(1, pool, tables(4800), rank=512) is None
    without = 4 * (32 * 4800 + 2 * 32 * 300 + 2 * 32 + 1)
    assert without <= paged_attention.SMEM_BYTES < without + 4 * (2 * 32 + 3 * 32 * 300 + 1)
    assert paged_attention.decode_shares(pool)
    assert not paged_attention.decode_shares(pool, window=256)
    # and the stacked rows follow from the head count: 256 query rows or more
    assert [paged_attention._latent_stacked_rows(H) for H in (8, 16, 32, 128)] == [32, 16, 8, 8]


@pytest.mark.parametrize("B,H", [(32, 32), (8, 128)], ids=["agent", "longdoc"])
def test_the_latent_decode_kernel_asks_for_no_more_vmem_than_a_kernel_gets(B, H):
    """Every row's waiting state and the stacked rows' are scratch: at both
    latent cells' shapes the call names no `vmem_limit_bytes` (a raised
    limit took XLA's cross-kernel weight prefetch from the whole step:
    PERF.md, PR 36) and is ONE `pallas_call`; that it fits what a v5e kernel
    gets unasked is the deviceless compile's to say
    (`tests/test_aot_topology.py -k latent`)."""
    sd = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(functools.partial(
        paged_attention._latent_decode_device.__wrapped__, scale=0.1, rank=512,
        interpret=False))(
        sd((B, H, 640), jnp.bfloat16), sd((16384, 16, 640), jnp.bfloat16),
        sd((B, 1024), jnp.int32), sd((B,), jnp.int32))
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes is None

    def held(shape, dtype):
        """Bytes in VMEM: the last two dimensions in whole tiles."""
        *lead, rows, lanes = (1,) + tuple(shape)
        tile = 32 // dtype.itemsize
        return int(np.prod(lead)) * -(-rows // tile) * tile * -(-lanes // 128) * 128 * dtype.itemsize

    scratch = sum(held(a.shape, a.dtype) for a in call.params["grid_mapping"].scratch_avals
                  if jnp.issubdtype(a.dtype, jnp.floating))
    bf16 = jnp.dtype(jnp.bfloat16)
    assert scratch + held((B, H, 640), bf16) + held((B, H, 512), bf16) <= 12 << 20


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 3e-5), (jnp.bfloat16, 7e-2)])
@pytest.mark.parametrize("L,start", [(64, 0), (64, 200), (32, 250), (16, 37)])
def test_the_latent_chunk_kernel_at_32_heads_is_the_masked_einsum(L, start, dtype, tol):
    """One head group of 32, YaRN's factor in the softmax scale."""
    scale = 192 ** -0.5 * 2.0048
    rng = np.random.default_rng(L + start)
    pool, tables = _pool_and_tables(rng, (start + L - 1,), dtype=dtype)
    pool = pool.at[..., 128 + 16:].set(0)  # a pool holds zeros behind a row's values
    operands = _chunk_operands(rng, 1, L, 32, dtype)
    got = latent_chunk_attention(*operands, pool, jnp.asarray(tables), jnp.asarray([start]),
                                 scale, interpret=True)
    want = _layer_reference(*operands, pool, tables, start + np.arange(L)[None], scale)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol)


@pytest.mark.parametrize("L,want", [(1, "latent_decode"), (512, "latent_chunk"),
                                    (128, "latent_chunk")])
def test_the_agent_cell_s_pool_takes_both_kernels(L, want):
    pool = jax.ShapeDtypeStruct((16384, 16, 640), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((32 if L == 1 else 1, 1024), jnp.int32)
    assert paged_kernel(L, pool, tables, rank=512) == want


# --- (vi) prefix sharing over latent blocks -----------------------------------

def full_kind():
    """A plain model of the full kind (K and V heads), for the same tests."""
    cfg = TransformerConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=96,
                            max_seq_len=M, use_flash=False)
    model = TransformerLM(cfg)
    return model, model.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))


@pytest.fixture(scope="module")
def kinds(small):
    return {"latent": small, "full": full_kind()}


def engine_of(model, variables, prefix_cache, pool_blocks=64, slots=3):
    engine = ServeEngine(model, variables, slots=slots, block_size=BS, pool_blocks=pool_blocks,
                         prefill_chunk_tokens=16, min_bucket=8, prefix_cache=prefix_cache)
    engine._prefill_chunk = Probe(engine._prefill_chunk)
    return engine


def run_all(engine, limit=600):
    steps = 0
    while engine.step():
        steps += 1
        assert steps < limit


def rows_of(engine, prompt, first=0):
    """{position: logits row} of `prompt`'s positions from `first` on, from
    the chunks the engine prefilled for it."""
    rows = {}
    for start, tokens, logits in engine._prefill_chunk.chunks:
        # a model without sparse layers pads with token 0, which `Probe` keeps
        real = tokens[:max(len(prompt) - start, 0)]
        if start >= first and len(real) and np.array_equal(
                real, prompt[start:start + len(real)]):
            rows.update({start + i: logits[i] for i in range(len(real))})
    return rows


def head_and_two_tails(head, seed):
    shared = tokens_of(head, seed)
    a, b = tokens_of(13, seed + 1), tokens_of(17, seed + 2)
    b[0] = (a[0] + 1) % SMALL["vocab_size"]  # the match ends with the head
    return np.concatenate([shared, a]), np.concatenate([shared, b])


@pytest.mark.parametrize("holder", ["decoding", "retired"])
@pytest.mark.parametrize("head", [48, 44])
@pytest.mark.parametrize("kind", ["latent", "full"])
def test_a_second_prompt_attaches_the_first_one_s_head_and_gives_the_unshared_logits(
        kinds, kind, head, holder):
    """The match ends on a block (48 = 6 blocks of 8) or inside one (44: the
    block is adopted and copied at the first write); the holder is still
    decoding beside the second request, or has retired and its blocks are
    parked under the index. Logits of the second prompt's own positions and
    its decoded tokens equal the run without sharing."""
    model, variables = kinds[kind]
    first, second = head_and_two_tails(head, 40 + head)
    plain = engine_of(model, variables, prefix_cache=False)
    plain.submit(second, 6, rid="second")
    run_all(plain)
    want = rows_of(plain, second)

    engine = engine_of(model, variables, prefix_cache=True)
    engine.submit(first, 40 if holder == "decoding" else 2, rid="first")
    if holder == "retired":
        run_all(engine)
        assert engine.cache.live_blocks == 0 and engine.cache.cached_free_blocks >= head // BS
    else:
        while "first" not in {r.rid for r in engine._slot_req if r is not None} or (
                engine._prefilling):
            engine.step()
    copied_at = []  # positions whose write copied a block first
    cow = engine.cache.cow_block

    def cow_block(slot, pos):
        before = engine.cache.cow_copies
        ok = cow(slot, pos)
        copied_at.extend([pos] * (engine.cache.cow_copies - before))
        return ok

    engine.cache.cow_block = cow_block
    engine.submit(second, 6, rid="second")
    run_all(engine)
    stats = engine.prefix.stats()
    assert stats["prefix_tokens_reused"] == head and stats["hits"] == 1
    assert stats["blocks_attached"] == -(-head // BS)
    # the second prompt's first write copies the block the match ends inside
    assert (head in copied_at) == bool(head % BS)
    got = rows_of(engine, second, first=head)
    assert sorted(got) == list(range(head, len(second)))  # only the tail was prefilled
    out = correctness.compare(np.stack([got[i] for i in sorted(got)]),
                              np.stack([want[i] for i in sorted(got)]), LIMITS)
    assert out["ok"], out
    assert engine.completions["second"].tokens == plain.completions["second"].tokens
    snap = engine.metrics.snapshot()["prefix_cache"]
    latent = kind == "latent"
    assert snap["prefix_latent_blocks_attached"] == (stats["blocks_attached"] if latent else 0)
    assert snap["prefix_latent_blocks_copied"] == (engine.cache.cow_copies if latent else 0)
    # every block went back: nothing is held, the head stays parked under the index
    assert engine.cache.live_blocks == 0 and engine.cache.total_block_refs == 0


def test_an_attached_prompt_s_logits_are_the_reference_s(small):
    model, variables = small
    first, second = head_and_two_tails(44, 90)
    engine = engine_of(model, variables, prefix_cache=True)
    engine.submit(first, 2, rid="first")
    run_all(engine)
    engine.submit(second, 5, rid="second")
    run_all(engine)
    got = rows_of(engine, second, first=44)
    want = reference_logits(variables, second, len(second) - 44)
    out = correctness.compare(np.stack([got[i] for i in sorted(got)]), want, LIMITS)
    assert out["ok"], out
    done = engine.completions["second"].tokens
    full = np.concatenate([second, np.asarray(done[:-1], np.int32)])
    assert correctness.chosen_gap(reference_logits(variables, full, len(done)), done) <= 1e-4


def test_attached_blocks_shifted_by_one_fail_the_comparison(small, monkeypatch):
    model, variables = small
    first, second = head_and_two_tails(48, 95)
    engine = engine_of(model, variables, prefix_cache=True)
    engine.submit(first, 2, rid="first")
    run_all(engine)
    attach = engine.cache.attach_prefix
    # the neighbours in the pool: the head's first block is lost, the
    # holder's own tail comes in (a rotation of the same blocks would pass:
    # rotated keys in another order are the same sum)
    monkeypatch.setattr(engine.cache, "attach_prefix",
                        lambda slot, blocks: attach(slot, [b + 1 for b in blocks]))
    engine.submit(second, 2, rid="second")
    run_all(engine)
    got = rows_of(engine, second, first=48)
    want = reference_logits(variables, second, len(second) - 48)
    out = correctness.compare(np.stack([got[i] for i in sorted(got)]), want,
                              {"max_rel": 1e-2, "rms_rel": 1e-2})
    assert not out["ok"], out


@pytest.mark.parametrize("kind", ["latent", "full"])
def test_a_parked_head_is_reclaimed_last_and_leaves_the_index_through_the_hook(kinds, kind):
    """A pool of 24 blocks: the holder's 8 blocks park under the index when
    it retires; two unrelated prompts of 12 blocks each then need more than
    the plain free list has, so the parked blocks are reclaimed (LRU) and
    the evict hook takes their nodes out of the index; a prompt with the
    old head then attaches less than the head, prefills the rest, and still
    decodes the unshared tokens."""
    model, variables = kinds[kind]
    first, second = head_and_two_tails(48, 70)
    engine = engine_of(model, variables, prefix_cache=True, pool_blocks=24, slots=2)
    evicted = []
    hook = engine.cache.evict_hook
    assert hook is not None
    engine.cache.evict_hook = lambda b: (evicted.append(b), hook(b))[1]
    engine.submit(first, 2, rid="first")
    run_all(engine)
    parked = engine.cache.cached_free_blocks
    assert parked == 8 and engine.prefix.stats()["nodes"] == 8
    for i in range(2):
        engine.submit(tokens_of(90, 200 + i), 3, rid=f"other{i}")
    run_all(engine)
    stats = engine.prefix.stats()
    assert evicted and stats["evicted_nodes"] >= len(evicted)
    assert engine.cache.live_blocks == 0
    before = stats["prefix_tokens_reused"]
    engine.submit(second, 4, rid="second")
    run_all(engine)
    reused = engine.prefix.stats()["prefix_tokens_reused"] - before
    assert reused < 48  # the head's first block went first: the chain is cut
    plain = engine_of(model, variables, prefix_cache=False)
    plain.submit(second, 4, rid="second")
    run_all(plain)
    assert engine.completions["second"].tokens == plain.completions["second"].tokens


@pytest.mark.parametrize("kind", ["latent", "full"])
def test_arrivals_behind_a_head_in_prefill_attach_it_and_do_not_compute_it(kinds, kind):
    """Three prompts with one head of 44 tokens arrive in one call, on a cold
    index: all miss at admission. Shortest-remaining-first prefills ONE of
    them whole; the other two look again before their first chunk, attach
    the head it indexed and prefill their tails alone. The counters stay one
    a request, and the tokens are the unshared run's."""
    model, variables = kinds[kind]
    shared = tokens_of(44, 300)
    prompts = [np.concatenate([shared, tokens_of(9 + 2 * i, 301 + i)]) for i in range(3)]
    runs = {}
    for share in (False, True):
        engine = engine_of(model, variables, prefix_cache=share)
        for i, prompt in enumerate(prompts):
            engine.submit(prompt, 5, rid=f"r{i}")
        attached = 0
        while engine.step():
            attached += engine.last_step.prefix_tokens_attached
        runs[share] = engine
        assert {r: c.tokens for r, c in engine.completions.items()} == {
            r: c.tokens for r, c in runs[False].completions.items()}
    stats = runs[True].prefix.stats()
    assert (stats["hits"], stats["misses"]) == (2, 1) and attached == 2 * 44
    assert stats["prefix_tokens_reused"] == 2 * 44
    # the head was computed once: two heads' worth of chunks less than unshared
    starts = sorted(start for start, _, _ in runs[True]._prefill_chunk.chunks)
    assert starts.count(0) == 1 and starts.count(44) == 2
    assert len(runs[False]._prefill_chunk.chunks) - len(starts) >= 4
    assert runs[True].cache.live_blocks == 0 and runs[True].cache.total_block_refs == 0


def test_rows_decoding_over_one_attached_head_read_it_once_and_decode_as_unshared():
    """What the chip's check cannot see (it decodes a group of ONE beside 31
    parked rows): four requests behind one 264-token head decode together
    through the latent decode kernel (a latent of 128 values, tables of 80
    pages of 8 keys: the head's first 32 pages are one whole compute block).
    With the prefix cache the three later requests attach the head, the
    step's kernel reads that block once for the rows decoding behind it, the
    engine counts those keys by the kernel's own rule, and every request's
    tokens are the engine's without a prefix cache."""
    from pytorch_distributed_example_tpu.serve.decode import layer_paths, step_shares_blocks

    config = dict(WIDE, num_hidden_layers=2)  # a dense layer and a sparse one
    model = modelglue.build_model(config, 640, remat=False)
    variables = modelglue.make_variables(model, config, seed=7)
    head = tokens_of(264, 500)
    prompts = [np.concatenate([head, tokens_of(5 + 4 * i, 501 + i)]) for i in range(4)]
    runs, records = {}, {}
    for share in (False, True):
        engine = ServeEngine(model, variables, slots=4, block_size=BS, pool_blocks=200,
                             prefill_chunk_tokens=128, min_bucket=8, prefix_cache=share)
        paths = layer_paths(engine.cache, 4, 1)
        assert set(paths) == {"latent"} and paths["latent"][1] == "latent_decode_kernel"
        assert step_shares_blocks(engine.cache, paths) and engine._decode_shares is share
        step, seen = engine._step, []

        def probe(params, tree, lengths, tokens, rngs, tables, step=step, engine=engine, seen=seen):
            seen.append(paged_attention.shared_decode_keys(
                np.asarray(tables), engine.cache.lengths, engine.cache.invalid_block, BS))
            return step(params, tree, lengths, tokens, rngs, tables)

        engine._step = probe
        for i, prompt in enumerate(prompts):
            engine.submit(prompt, 4, rid=f"r{i}")
        booked = []
        while engine.step():
            if engine.last_step.decode_keys:
                booked.append(engine.last_step.decode_shared_keys)
        runs[share], records[share] = engine, (booked, seen)
    assert {r: c.tokens for r, c in runs[True].completions.items()} == {
        r: c.tokens for r, c in runs[False].completions.items()}
    assert runs[True].prefix.stats()["prefix_tokens_reused"] == 3 * 264
    booked, seen = records[True]
    assert booked == seen and max(booked) == 4 * 256  # all four rows over the one block
    # without a prefix cache no two tables hold one page, and nothing is counted
    assert not any(records[False][0]) and not any(records[False][1])
    assert runs[True].metrics.snapshot()["decode"]["kernel_share"] == 1.0


REFUSED = {
    "kv_quant": dict(kv_quant=True),
    "mesh": dict(mesh=object()),
    "role": dict(role="prefill"),
    "precompiled": dict(precompiled={"step": object()}),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_the_other_four_refusals_of_the_latent_kind_stand(small, what):
    model, variables = small
    with pytest.raises(ValueError, match="latent layers cannot be served with " + what):
        ServeEngine(model, variables, slots=2, block_size=BS, pool_blocks=40,
                    prefill_chunk_tokens=16, min_bucket=8, prefix_cache=True, **REFUSED[what])
