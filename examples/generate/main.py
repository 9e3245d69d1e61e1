"""Autoregressive generation with the KV-cache decode path.

Train-then-sample demo: fit a small TransformerLM on a repeating token
pattern (or bytes of --data), then generate continuations with the
two-program KV-cache loop (`models/generate.py`). Shows the full
inference surface: greedy vs temperature/top-k sampling, EOS stop, and
decode throughput.

Run:  python examples/generate/main.py --steps 200 --new 48
      python examples/generate/main.py --temperature 0.8 --top-k 20
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200, help="train steps")
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new", type=int, default=32, help="tokens to generate")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--data", type=str, default=None, help="text file (bytes)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="force the virtual CPU backend")
    args = ap.parse_args()

    import os

    import jax

    if getattr(args, "cpu", False) or os.environ.get("TDX_EXAMPLES_CPU"):
        from pytorch_distributed_example_tpu._compat import force_cpu_devices

        force_cpu_devices(int(os.environ.get("TDX_EXAMPLES_CPU_DEVICES", "2")))
    from pytorch_distributed_example_tpu._compat import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp
    import optax

    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
        generate,
    )

    if args.data:
        data = np.frombuffer(Path(args.data).read_bytes(), dtype=np.uint8)
        vocab = 256
        need = max(args.seq + 2, args.prompt_len + args.new + 1)
        if len(data) < need:
            ap.error(
                f"--data has {len(data)} bytes; need >= {need} for "
                f"--seq {args.seq} / --prompt-len {args.prompt_len} "
                f"--new {args.new}"
            )
    else:
        # a periodic pattern the model can nail — makes the demo legible
        base = np.arange(16, dtype=np.int32)
        data = np.tile(np.concatenate([base, base[::-1]]), 512)
        vocab = 32

    cfg = TransformerConfig(
        vocab_size=vocab, d_model=128, n_layers=2, n_heads=4,
        max_seq_len=args.prompt_len + args.new, use_flash=False,
    )
    model = TransformerLM(cfg)
    gen = np.random.default_rng(args.seed)
    toks0 = jnp.zeros((1, args.seq), jnp.int32)
    params = model.init(jax.random.PRNGKey(args.seed), toks0)
    opt = optax.adamw(3e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, toks):
        def lf(p):
            lg = model.apply(p, toks)
            return optax.softmax_cross_entropy_with_integer_labels(
                lg[:, :-1], toks[:, 1:]
            ).mean()

        loss, grads = jax.value_and_grad(lf)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    for i in range(args.steps):
        starts = gen.integers(0, len(data) - args.seq - 1, args.batch)
        toks = jnp.asarray(
            np.stack([data[s : s + args.seq] for s in starts]), jnp.int32
        )
        params, opt_state, loss = step(params, opt_state, toks)
        if i % 50 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {float(loss):.4f}")

    # leave room for the full ground-truth continuation after the prompt
    s = int(gen.integers(0, len(data) - args.prompt_len - args.new))
    prompt = jnp.asarray(data[s : s + args.prompt_len], jnp.int32)[None]
    t0 = time.perf_counter()
    out = generate(
        model, params, prompt, args.new,
        temperature=args.temperature, top_k=args.top_k,
        rng=jax.random.PRNGKey(args.seed + 1),
    )
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0

    cont = np.asarray(out)[0]
    truth = data[s + args.prompt_len : s + args.prompt_len + args.new]
    acc = float((cont == truth[: len(cont)]).mean()) if not args.data else None
    print("prompt:     ", np.asarray(prompt)[0].tolist())
    print("generated:  ", cont.tolist())
    print(f"{args.new} tokens in {dt*1e3:.0f} ms "
          f"({args.new / dt:.1f} tok/s)")
    if acc is not None:
        print(f"pattern accuracy: {acc:.0%}")


if __name__ == "__main__":
    main()
