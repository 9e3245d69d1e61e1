"""Toy models of the four kinds of layer pattern the engine serves, and one
small mixed workload over them: what `tests/test_serve_pipeline.py` serves,
and what `tests/fixtures/serve_pipeline_streams.json` was recorded from at
the commit before `ServeEngine.step` kept a decode step in flight.
"""

import copy
import json
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
M, BS = 96, 4
KINDS = ("dense", "sparse_window", "linear")
SAMPLING = {"greedy": dict(temperature=0.0), "seeded": dict(temperature=0.8, top_k=8)}
CHUNKING = {"chunked": 8, "unchunked": None}
# (prompt tokens, token budget): a budget of one, one longer than two chunks
WORKLOAD = ((5, 6), (11, 9), (3, 1), (19, 12), (8, 4), (6, 2))


def _published(name):
    return json.loads((ROOT / "bench_matrix" / "configs" / name).read_text())


def config_of(kind):
    """The published file of each accepted configuration cut to a toy."""
    float32 = {"weights": "float32", "activations": "float32", "kv_cache": "float32"}
    if kind == "sparse_window":
        pub = _published("laguna-xs.2-d5.json")
        small = dict(
            pub, hidden_size=64, head_dim=16, num_attention_heads=6,
            num_key_value_heads=2, num_attention_heads_per_layer=[6, 8, 8, 8, 6],
            intermediate_size=96, vocab_size=128, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            sliding_window=8, dtype=float32,
        )
        small["rope_parameters"] = copy.deepcopy(pub["rope_parameters"])
        small["rope_parameters"]["full_attention"].update(
            original_max_position_embeddings=16, factor=8.0)
        return small
    if kind == "linear":
        pub = _published("olmo-hybrid-7b-d16.json")
        return dict(
            pub, hidden_size=48, num_attention_heads=3, num_key_value_heads=3,
            intermediate_size=64, vocab_size=128, num_hidden_layers=4,
            layer_types=pub["layer_types"][:4], linear_num_key_heads=3,
            linear_num_value_heads=3, linear_key_head_dim=8, linear_value_head_dim=12,
            dtype=dict(float32, recurrent_state="float32"),
        )
    raise ValueError(kind)


def build(kind):
    """(model, variables, vocabulary) of one toy."""
    if kind == "dense":
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import TransformerConfig, TransformerLM

        model = TransformerLM(TransformerConfig(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, max_seq_len=M,
            use_flash=False))
        return model, model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)), 64
    from bench_matrix import modelglue

    config = config_of(kind)
    model = modelglue.build_model(config, M, remat=False)
    return model, modelglue.make_variables(model, config, seed=7), config["vocab_size"]


def requests(vocab):
    """[(rid, prompt, budget, seed)] of the workload."""
    gen = np.random.default_rng(11)
    return [(f"r{i}", gen.integers(0, vocab, (n,)).astype(np.int32), budget, 100 + i)
            for i, (n, budget) in enumerate(WORKLOAD)]


def engine_of(model, variables, sampling, chunking, **kw):
    from pytorch_distributed_example_tpu.serve import ServeEngine

    args = dict(slots=3, block_size=BS, pool_blocks=3 * M // BS, min_bucket=4,
                prefill_chunk_tokens=CHUNKING[chunking], **SAMPLING[sampling])
    args.update(kw)
    return ServeEngine(model, variables, **args)


def serve(model, variables, vocab, sampling, chunking, **kw):
    """rid -> tokens of the workload through one engine."""
    engine = engine_of(model, variables, sampling, chunking, **kw)
    for rid, prompt, budget, seed in requests(vocab):
        engine.submit(prompt, budget, rid=rid, seed=seed)
    done = engine.run(max_steps=500)
    return {rid: [int(t) for t in c.tokens] for rid, c in done.items()}
