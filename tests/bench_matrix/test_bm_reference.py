"""The plain float32 reference against the system at a tiny size, on seeded
weights, with grouped-query attention (4 heads over 2 KV heads):
`TransformerLM.apply`, the `fully_shard` forward on four devices, and
`ServeEngine` prefill and decode through the paged cache."""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_matrix import correctness, modelglue, spec, traffic_gen
from bench_matrix.reference import decoder

from _tiny import TINY_MODEL, context, tiny_cell

# float32 on both sides: what is left is summation order
TIGHT = {"max_rel": 2e-5, "rms_rel": 2e-5}


@pytest.fixture(scope="module")
def tiny():
    cfg = copy.deepcopy(spec.load("configs", "mistral-7b-v0.3-d4"))
    cfg.update(TINY_MODEL)
    cfg["dtype"] = dict(cfg["dtype"], weights="float32", activations="float32")
    model = modelglue.build_model(cfg, 128, remat=False)
    variables = modelglue.make_variables(model, cfg, seed=7)
    tokens = traffic_gen.check_sequence(cfg["vocab_size"], 7, 64)
    want = correctness.reference_logits(cfg, variables, tokens, 64)
    return cfg, model, variables, tokens, want


def test_the_test_really_has_grouped_query_attention(tiny):
    cfg, model, variables, _, _ = tiny
    attn = variables["params"]["layers_0"]["attn"]
    assert attn["q_proj"]["kernel"].shape == (64, 64)
    assert attn["k_proj"]["kernel"].shape == (64, 32)
    assert model.cfg.n_heads == 4 and model.cfg.kv_heads == 2


def test_model_apply_agrees_with_the_reference(tiny):
    _, model, variables, tokens, want = tiny
    got = model.apply(variables, jnp.asarray(tokens)[None])[0]
    assert correctness.compare(got, want, TIGHT)["ok"]
    # and the comparison can fail: a dropped residual term is not inside it
    broken = jax.tree_util.tree_map(lambda x: x, variables)
    broken["params"]["layers_1"]["attn"]["o_proj"]["kernel"] *= 0
    bad = model.apply(broken, jnp.asarray(tokens)[None])[0]
    assert not correctness.compare(bad, want, {"max_rel": 0.05, "rms_rel": 0.05})["ok"]


def test_reference_is_causal_and_blocks_do_not_change_it(tiny, monkeypatch):
    cfg, _, variables, tokens, want = tiny
    short = correctness.reference_logits(cfg, variables, tokens[:40], 40)
    np.testing.assert_allclose(short, want[:40], rtol=1e-4, atol=1e-5)
    monkeypatch.setattr(decoder, "QUERY_BLOCK", 16)
    decoder.layer.clear_cache()
    blocked = correctness.reference_logits(cfg, variables, tokens, 64)
    decoder.layer.clear_cache()
    np.testing.assert_allclose(blocked, want, rtol=1e-4, atol=1e-5)


def test_fully_shard_forward_agrees_with_the_reference(tiny):
    cfg, model, _, tokens, want = tiny
    traffic = copy.deepcopy(spec.load("traffic", "train_s4096_b4_fsdp4"))
    trainer = spec.module("trainers", "fsdp").Trainer(
        model, cfg, traffic, 7, jax.devices()[:4])
    assert trainer.rows == 4
    q = trainer.params["params"]["layers_0"]["attn"]["q_proj"]["kernel"]
    assert q.sharding.shard_shape(q.shape) == (16, 64)  # born sharded over fsdp
    x = trainer.place(np.tile(tokens[None], (4, 1)))
    got = trainer.forward(trainer.params, x)[0]
    assert correctness.compare(got, want, TIGHT)["ok"]


def test_serve_engine_prefill_and_decode_agree_with_the_reference(tiny):
    from bench_matrix.runners import serve
    from pytorch_distributed_example_tpu.serve import ServeEngine

    cfg, model, variables, _, _ = tiny
    cell = tiny_cell("serve_decode_c32")
    cell["config"] = cfg
    cell["correctness"].update(TIGHT, chosen_gap=1e-5)
    eng = dict(cell["traffic"]["engine"])
    eng.pop("max_seq_len")
    engine = ServeEngine(model, variables, **eng)
    said = []
    ctx = context(1.0, jax.devices()[:1], seed=7)
    ctx.say = said.append
    assert serve._check(cell, ctx, engine, variables), said
    # 64 prompt tokens in two 32-token chunks, then 4 decoded positions
    assert "prefill of 64 tokens" in said[0] and "4 decoded positions" in said[0]
    cell["correctness"]["max_rel"] = 1e-9
    assert not serve._check(cell, ctx, engine, variables)


def test_chosen_gap_sees_a_wrong_token():
    ref = np.array([[0.0, 1.0, 5.0], [4.0, -4.0, 0.0]])
    assert correctness.chosen_gap(ref, [2, 0]) == 0.0
    assert correctness.chosen_gap(ref, [2, 1]) == pytest.approx(8.0 / 5.0)
