"""Autoregressive generation — KV-cache decode loop for TransformerLM.

TPU-idiomatic inference: exactly TWO compiled programs regardless of
length — one prefill (whole prompt through the cache path) and one
decode body (single token), the decode loop a `lax.scan` so sampling,
cache updates, and EOS bookkeeping all live on device. The jitted
programs are cached per (model, sampling knobs), NOT per call, so a
serving loop pays compilation once; the empty KV cache is built
directly from the config (no model trace on the request path). Static shapes
throughout: the cache is (B, max_seq_len) from construction and the
output is always (B, max_new_tokens), EOS-padded.

Sampling: greedy (temperature=0), temperature softmax, optional top-k
truncation — the standard generate() knobs.
"""

from __future__ import annotations

from typing import Any, Optional


import functools


def sample_logits(logits, rng, temperature: float, top_k: Optional[int]):
    """The shared sampling head: greedy (temperature=0), temperature
    softmax, optional top-k truncation. `logits` is (..., vocab); one
    rng samples the whole batch. The serve engine's slot batch vmaps
    this over per-slot keys (`serve/decode.py`)."""
    import jax
    import jax.numpy as jnp

    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        k = min(top_k, logits.shape[-1])  # HF convention: clamp to vocab
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


_sample = sample_logits  # decode-loop-internal alias (pre-serve name)


@functools.lru_cache(maxsize=32)
def _programs(model, temperature: float, top_k: Optional[int], eos_id):
    """Jitted prefill/decode pair per (model, sampling knobs). flax
    Modules are frozen dataclasses — hashable, equal by config — so the
    lru_cache dedupes equal-config models AND bounds growth (each entry
    anchors compiled XLA executables)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def prefill(params, cache, prompt, rng):
        logits, vars2 = model.apply(
            {"params": params, "cache": cache}, prompt, decode=True,
            mutable=["cache"],
        )
        rng, sub = jax.random.split(rng)
        tok = _sample(logits[:, -1], sub, temperature, top_k)
        return vars2["cache"], tok, rng

    @functools.partial(jax.jit, static_argnums=(4,))
    def decode(params, cache, first, rng, length):
        def step(carry, _):
            cache, tok, done, rng = carry
            logits, vars2 = model.apply(
                {"params": params, "cache": cache}, tok[:, None],
                decode=True, mutable=["cache"],
            )
            rng, sub = jax.random.split(rng)
            nxt = _sample(logits[:, -1], sub, temperature, top_k)
            if eos_id is not None:
                done = jnp.logical_or(done, tok == eos_id)
                nxt = jnp.where(done, eos_id, nxt)
            return (vars2["cache"], nxt, done, rng), nxt

        done = jnp.zeros(first.shape, bool)
        _, rest = lax.scan(step, (cache, first, done, rng), None, length=length)
        return rest.T  # (B, length)

    return prefill, decode


def init_cache(model, batch_size: int):
    """Empty KV cache for `model` at this batch size — built directly
    from the config, no model trace on the request path: per layer, under
    its mixer's name, the leaves the mixer keeps
    (`models.transformer.cache_leaves`) — (B, max_seq_len) entries and an
    `index` where it keeps one a token, (B,) where it keeps one a row. The
    structure mirrors the module tree; `test_generate.py` pins it against
    `model.init(decode=True)` so drift fails loudly."""
    import jax.numpy as jnp

    from .transformer import cache_leaves, layers_of

    cfg = model.cfg
    tree = {}
    for i, kind in enumerate(layers_of(cfg)):
        mixer, span, leaves = cache_leaves(cfg, kind)
        lead = (batch_size,) if span == "row" else (batch_size, cfg.max_seq_len)
        layer = {
            leaf: jnp.zeros(lead + shape, dtype)
            for leaf, (shape, dtype) in leaves.items()
        }
        if span != "row":
            layer["index"] = jnp.zeros((), jnp.int32)
        tree[f"layers_{i}"] = {mixer: layer}
    return tree


def generate(
    model,
    params,
    prompt,
    max_new_tokens: int,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    rng: Optional[Any] = None,
    eos_id: Optional[int] = None,
):
    """Generate `max_new_tokens` continuations of `prompt` (B, L_p).

    Returns (B, max_new_tokens) int32. With `eos_id`, sequences freeze at
    EOS (subsequent positions filled with eos_id); generation still runs
    the full static length — the XLA-friendly trade.
    """
    import jax
    import jax.numpy as jnp

    cfg = model.cfg
    B, L_p = prompt.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if L_p + max_new_tokens > cfg.max_seq_len:
        raise ValueError(
            f"prompt ({L_p}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_seq_len ({cfg.max_seq_len})"
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    p = params["params"] if "params" in params else params

    prefill, decode = _programs(model, temperature, top_k, eos_id)
    cache = init_cache(model, B)
    cache, first, rng = prefill(p, cache, prompt, rng)
    if max_new_tokens == 1:
        return first[:, None]
    rest = decode(p, cache, first, rng, max_new_tokens - 1)
    return jnp.concatenate([first[:, None], rest], axis=1)
