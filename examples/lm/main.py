"""TransformerLM training over a (dp, fsdp, tp) mesh — the framework's
flagship workload (BASELINE.json configs #4/#5 shape).

Causal LM on synthetic token streams (or a text file via --data): the full
train step — forward, backward, optimizer — is one jit-compiled program
whose parameter layout comes from `transformer_sharding_rules` (2-D
Megatron+ZeRO GSPMD); XLA inserts and overlaps every collective.

Run:  python examples/lm/main.py --steps 50 --d-model 256 --n-layers 4
      python examples/lm/main.py --tp 2 --bf16 --n-experts 8   # MoE + TP
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np


def batches(data: np.ndarray, batch: int, seq: int, seed: int):
    gen = np.random.default_rng(seed)
    while True:
        starts = gen.integers(0, len(data) - seq - 1, batch)
        yield np.stack([data[s : s + seq] for s in starts]).astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=None, help="text file (bytes as tokens); synthetic if unset")
    ap.add_argument("--vocab-size", type=int, default=512)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--n-layers", type=int, default=4)
    ap.add_argument("--n-heads", type=int, default=8)
    ap.add_argument("--n-experts", type=int, default=0)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch-size", type=int, default=16, help="global batch")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--no-flash", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
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

    from pytorch_distributed_example_tpu.mesh import init_device_mesh
    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
        transformer_sharding_rules,
    )
    from pytorch_distributed_example_tpu.parallel import fully_shard

    n_dev = len(jax.devices())
    tp = args.tp
    fsdp = n_dev // tp
    mesh = init_device_mesh(("fsdp", "tp"), (fsdp, tp))
    print(f"devices={n_dev} mesh=fsdp{fsdp}xtp{tp}")

    if args.data:
        data = np.frombuffer(Path(args.data).read_bytes(), dtype=np.uint8)
        vocab = 256
    else:
        gen = np.random.default_rng(0)
        # markovian synthetic stream so the LM has learnable structure
        data = np.cumsum(gen.integers(1, 7, 200_000)) % args.vocab_size
        vocab = args.vocab_size

    cfg = TransformerConfig(
        vocab_size=vocab,
        d_model=args.d_model,
        n_layers=args.n_layers,
        n_heads=args.n_heads,
        n_experts=args.n_experts,
        max_seq_len=args.seq,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        use_flash=not args.no_flash,
        remat=args.remat,
    )
    model = TransformerLM(cfg)
    it = batches(data, args.batch_size, args.seq + 1, 1)
    toks0 = jnp.asarray(next(it)[:, : args.seq])
    params = model.init(jax.random.PRNGKey(0), toks0[:1])
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))

    mod = fully_shard(
        model, params, mesh, axis="fsdp",
        rules=transformer_sharding_rules("tp", "fsdp"),
        data_axes=("fsdp",),
    )
    opt = optax.adamw(args.lr)

    def loss_fn(logits, y):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], y[:, 1:]
        ).mean()

    step = mod.make_train_step(opt, loss_fn)
    opt_state = opt.init(mod.params)

    p, s = mod.params, opt_state
    print(f"params: {n_params/1e6:.1f}M  starting {args.steps} steps")
    t0 = time.perf_counter()
    tokens_done = 0
    for i in range(1, args.steps + 1):
        chunk = jnp.asarray(next(it)[:, : args.seq])
        p, s, loss = step(p, s, chunk, chunk)
        tokens_done += args.batch_size * args.seq
        if i % args.log_every == 0 or i == args.steps:
            jax.block_until_ready(loss)
            dt = time.perf_counter() - t0
            print(
                f"step {i}/{args.steps}  loss {float(loss):.4f}  "
                f"{tokens_done / dt:.0f} tok/s ({tokens_done / dt / n_dev:.0f}/chip)"
            )


if __name__ == "__main__":
    main()
