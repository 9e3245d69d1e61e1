"""KV-cache decode + generation tests (`models/generate.py`,
`models/transformer.py` decode path). The load-bearing check: prefill +
one-token decode steps must reproduce the full causal forward's logits
exactly (same params, same positions) — cache indexing, absolute-RoPE,
and masking all have to line up for that to hold."""

import numpy as np
import pytest


def _model(n_kv_heads=None, max_seq_len=32, **pattern):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
    )

    cfg = TransformerConfig(
        vocab_size=64,
        d_model=32,
        n_layers=2,
        n_heads=4,
        n_kv_heads=n_kv_heads,
        max_seq_len=max_seq_len,
        use_flash=False,
        **pattern,
    )
    model = TransformerLM(cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 8)))
    params = model.init(jax.random.PRNGKey(0), toks)
    return model, params, toks


def _layer_kinds():
    """Toy two-layer configurations, one per mechanism a layer pattern
    adds to the cached attention step, and one with all of them."""
    from pytorch_distributed_example_tpu.models.transformer import (
        LayerSpec,
        RopeSpec,
    )

    sparse = dict(
        sparse_experts=4, sparse_top_k=2, sparse_d_ff=16, shared_d_ff=16
    )
    return dict(
        plain={},
        window=dict(layers=(LayerSpec("window"), LayerSpec()), window=3),
        head_gate=dict(layers=(LayerSpec(), LayerSpec()), attn_gate=True),
        halves_partial_rope=dict(
            layers=(
                LayerSpec(rope=RopeSpec(10000.0, 0.5)),
                LayerSpec(rope=RopeSpec(500000.0, 0.5)),
            ),
            rope_pairs="halves",
        ),
        per_layer_heads=dict(
            n_kv_heads=2, head_size=8,
            layers=(LayerSpec(n_heads=4), LayerSpec(n_heads=6)),
        ),
        sparse_mlp=dict(layers=(LayerSpec(), LayerSpec(mlp="sparse")), **sparse),
        every=dict(
            n_kv_heads=2, head_size=8, window=3, attn_gate=True,
            rope_pairs="halves", **sparse,
            layers=(
                LayerSpec("window", 6, RopeSpec(10000.0, 1.0), "sparse"),
                LayerSpec("full", 4, RopeSpec(500000.0, 0.5), "dense"),
            ),
        ),
    )


class TestDecodeParity:
    @pytest.mark.parametrize(
        "kind",
        ["plain", "window", "head_gate", "halves_partial_rope",
         "per_layer_heads", "sparse_mlp"],
    )
    def test_incremental_decode_matches_full_forward(self, kind):
        """Prefill(prompt[:4]) + 4 single-token steps == causal forward,
        for each mechanism a layer can carry: what entitles `generate()`
        to be the serve tests' reference for a patterned model. The
        window (3) is shorter than the prompt, so keys fall out of it
        both in the prefill and while decoding."""
        import jax
        import jax.numpy as jnp

        model, params, toks = _model(**_layer_kinds()[kind])
        p = params["params"]
        full = model.apply(params, toks)  # (2, 8, 64) causal logits

        cache = model.init(
            jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32), decode=True
        )["cache"]
        lg, v = model.apply(
            {"params": p, "cache": cache}, toks[:, :4], decode=True,
            mutable=["cache"],
        )
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(full[:, :4]), rtol=2e-4, atol=2e-5
        )
        cache = v["cache"]
        for i in range(4, 8):
            lg, v = model.apply(
                {"params": p, "cache": cache}, toks[:, i : i + 1],
                decode=True, mutable=["cache"],
            )
            cache = v["cache"]
            np.testing.assert_allclose(
                np.asarray(lg[:, 0]), np.asarray(full[:, i]),
                rtol=2e-4, atol=2e-5,
            )

    @pytest.mark.slow  # heavy compile: full-suite only (<2 min habit run)
    def test_gqa_decode_matches_full_forward(self):
        import jax
        import jax.numpy as jnp

        model, params, toks = _model(n_kv_heads=2)
        p = params["params"]
        full = model.apply(params, toks)
        cache = model.init(
            jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32), decode=True
        )["cache"]
        lg, v = model.apply(
            {"params": p, "cache": cache}, toks, decode=True, mutable=["cache"]
        )
        np.testing.assert_allclose(
            np.asarray(lg), np.asarray(full), rtol=2e-4, atol=2e-5
        )


class TestGenerate:
    @pytest.mark.slow  # heavy compile/convergence; full suite only
    def test_greedy_matches_stepwise_argmax(self):
        """generate(temperature=0) == manual argmax continuation via the
        full forward (the no-cache oracle)."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import generate

        model, params, toks = _model()
        prompt = toks[:, :5]
        out = generate(model, params, prompt, max_new_tokens=6)
        assert out.shape == (2, 6)

        seq = np.asarray(prompt)
        for _ in range(6):
            lg = model.apply(params, jnp.asarray(seq))
            nxt = np.argmax(np.asarray(lg[:, -1]), axis=-1)
            seq = np.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(out), seq[:, 5:])

    def test_greedy_on_a_patterned_model_matches_stepwise_argmax(self):
        """The same oracle for a model that carries every mechanism of
        `_layer_kinds` at once: `generate()`'s two programs against the
        cache-free forward, token for token. The model is causal, so one
        forward over prompt + output gives every step's logits."""
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import generate

        model, params, toks = _model(**_layer_kinds()["every"])
        prompt = toks[:, :5]
        out = np.asarray(generate(model, params, prompt, max_new_tokens=6))

        seq = np.concatenate([np.asarray(prompt), out], axis=1)
        lg = np.asarray(model.apply(params, jnp.asarray(seq)))
        # logits at position t choose token t + 1
        np.testing.assert_array_equal(np.argmax(lg[:, 4:-1], axis=-1), out)

    def test_sampling_reproducible_and_topk_bounded(self):
        import jax

        from pytorch_distributed_example_tpu.models import generate

        model, params, toks = _model()
        prompt = toks[:, :4]
        a = generate(
            model, params, prompt, 5, temperature=0.8, top_k=8,
            rng=jax.random.PRNGKey(7),
        )
        b = generate(
            model, params, prompt, 5, temperature=0.8, top_k=8,
            rng=jax.random.PRNGKey(7),
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        c = generate(
            model, params, prompt, 5, temperature=0.8, top_k=8,
            rng=jax.random.PRNGKey(8),
        )
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_eos_freezes_sequence(self):
        """Once a row emits eos, every later position is eos — pick the
        eos id FROM a greedy run so the freeze path is guaranteed to
        fire (a vacuous no-eos pass would hide regressions)."""
        from pytorch_distributed_example_tpu.models import generate

        model, params, toks = _model()
        free = np.asarray(generate(model, params, toks[:, :4], 12))
        eos = int(free[0, 2])  # token row 0 actually emits at step 2
        out = np.asarray(
            generate(model, params, toks[:, :4], 12, eos_id=eos)
        )
        hits0 = np.where(out[0] == eos)[0]
        assert len(hits0) > 0  # the chosen eos fires for row 0
        for row in out:
            hits = np.where(row == eos)[0]
            if len(hits):
                assert (row[hits[0] :] == eos).all()

    def test_program_cache_reused_across_calls(self):
        """Two same-knob generate() calls share the cached jitted
        programs (lru_cache keyed on the hashable model)."""
        import jax

        from pytorch_distributed_example_tpu.models import generate
        from pytorch_distributed_example_tpu.models.generate import _programs

        model, params, toks = _model()
        generate(model, params, toks[:, :4], 3, rng=jax.random.PRNGKey(0))
        before = _programs.cache_info().hits
        generate(model, params, toks[:, :4], 3, rng=jax.random.PRNGKey(1))
        assert _programs.cache_info().hits > before

    def test_init_cache_matches_model_structure(self):
        """The config-derived cache tree must stay bit-identical in
        structure/shape/dtype to what the model's own init creates."""
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import init_cache

        model, params, toks = _model(n_kv_heads=2)
        want = jax.eval_shape(
            lambda: model.init(
                jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32),
                decode=True,
            )
        )["cache"]
        got = init_cache(model, 2)
        wl, wt = jax.tree_util.tree_flatten(
            jax.tree_util.tree_map(lambda s: (s.shape, str(s.dtype)), want)
        )
        gl, gt = jax.tree_util.tree_flatten(
            jax.tree_util.tree_map(
                lambda a: (a.shape, str(a.dtype)), got
            )
        )
        assert wt == gt and wl == gl

    def test_topk_clamped_to_vocab(self):
        from pytorch_distributed_example_tpu.models import generate

        model, params, toks = _model()
        out = generate(
            model, params, toks[:, :4], 3, temperature=0.9, top_k=10_000
        )
        assert out.shape == (2, 3)

    def test_decode_rejected_for_non_causal(self):
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import (
            TransformerConfig,
            TransformerLM,
        )

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=1, n_heads=4,
            max_seq_len=16, causal=False, use_flash=False,
        )
        model = TransformerLM(cfg)
        with pytest.raises(ValueError, match="causal"):
            model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32),
                decode=True,
            )

    def test_length_budget_enforced(self):
        from pytorch_distributed_example_tpu.models import generate

        model, params, toks = _model(max_seq_len=16)
        with pytest.raises(ValueError):
            generate(model, params, toks[:, :8], max_new_tokens=9)
