"""Training recipes shared by tests."""

from __future__ import annotations


def chain_pretrain(
    model,
    params,
    train_len: int,
    vocab_cap: int = 256,
    steps: int = 300,
    loss_floor: float = 0.01,
    seed: int = 1,
    batch: int = 16,
):
    """Briefly pretrain a `TransformerLM` on the deterministic bigram
    chain ``next = (5 t + 17) mod V`` and return
    ``(params, chain_fn, final_loss)``.

    For the int8-KV parity tests:
    greedy decode on random-init weights argmaxes over near-tied logits
    (top-2 gaps of order 1e-3), so ANY lossy cache — int8, even bf16 —
    flips tokens at ~2%/token there, measuring argmax noise rather than
    cache fidelity. Training to `loss_floor` at the FULL `train_len`
    the caller will decode to (RoPE positions the model never saw stay
    near-tied too) gives the margins a trained model has; a token
    match rate then measures quantization-induced flips, which is the
    claim. `chain_fn(start, length)` regenerates the data stream for
    prompts."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    V = min(model.cfg.vocab_size, vocab_cap)

    def chain(start, length):
        out = np.empty(length, np.int64)
        out[0] = start % V
        for j in range(1, length):
            out[j] = (5 * out[j - 1] + 17) % V
        return out.astype(np.int32)

    opt = optax.adam(1e-2)

    @jax.jit
    def train_step(p, o, b):
        def loss_fn(pp):
            logits = model.apply(pp, b[:, :-1])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, b[:, 1:]
            ).mean()

        l, grads = jax.value_and_grad(loss_fn)(p)
        up, o = opt.update(grads, o, p)
        return optax.apply_updates(p, up), o, l

    rng = np.random.default_rng(seed)
    o, loss = opt.init(params), None
    for _ in range(steps):
        b = np.stack(
            [chain(int(rng.integers(0, V)), train_len) for _ in range(batch)]
        )
        params, o, loss = train_step(params, o, jnp.asarray(b))
        if float(loss) < loss_floor:
            break
    return params, chain, float(loss)
