"""ResNet-18 / CIFAR-10 DDP training (BASELINE.json config #3 workload).

Same shape as examples/mnist/main.py but with real compute per step:
ResNet-18 (NHWC, BatchNorm), per-rank DistributedSampler sharding packed
rank-major into the global batch, gradients and BatchNorm statistics
pmean'd inside the one compiled train step.

Run:  python examples/cifar/main.py --epochs 2 --batch-size 128
      (synthetic CIFAR unless --root points at a CIFAR-10 binary dir)
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np


def synthetic_cifar(n: int, seed: int):
    gen = np.random.default_rng(seed)
    x = gen.standard_normal((n, 32, 32, 3)).astype(np.float32)
    w = gen.standard_normal((32 * 32 * 3, 10)).astype(np.float32)
    y = (x.reshape(n, -1) @ w).argmax(1).astype(np.int32)
    return x, y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="xla")
    ap.add_argument("--init-method", default=None)
    ap.add_argument("--world-size", type=int, default=-1)
    ap.add_argument("--rank", type=int, default=-1)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=128, help="per-rank batch")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--train-size", type=int, default=4096)
    ap.add_argument("--test-size", type=int, default=1024)
    ap.add_argument("--bf16", action="store_true")
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

    import pytorch_distributed_example_tpu as tdx
    from pytorch_distributed_example_tpu.data import DataLoader
    from pytorch_distributed_example_tpu.models import (
        ResNet18,
        convert_sync_batchnorm,
    )
    from pytorch_distributed_example_tpu._compat import shard_map_fn
    from jax.sharding import PartitionSpec as P

    tdx.init_process_group(
        backend=args.backend,
        init_method=args.init_method,
        world_size=args.world_size,
        rank=args.rank,
    )
    W = tdx.get_world_size()
    print(f"backend={tdx.get_backend()} world_size={W} devices={jax.devices()[:W]}")

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    # sync BN: normalize with global batch statistics (torch's
    # DDP + SyncBatchNorm recipe); stats agree across ranks by design
    model = convert_sync_batchnorm(
        ResNet18(num_classes=10, dtype=dtype), axis_name="_ranks"
    )
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
    opt = optax.sgd(args.lr, momentum=args.momentum)

    mesh = tdx.distributed._get_default_group().mesh.jax_mesh

    def local_step(params, batch_stats, opt_state, x, y):
        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": batch_stats},
                x, train=True, mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
            return loss, mut["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads = jax.tree_util.tree_map(lambda g: jax.lax.pmean(g, "_ranks"), grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_stats, opt_state, jax.lax.pmean(loss, "_ranks")

    step = jax.jit(
        shard_map_fn(
            local_step,
            mesh=mesh,
            in_specs=(P(), P(), P(), P("_ranks"), P("_ranks")),
            out_specs=(P(), P(), P(), P()),
        ),
        donate_argnums=(0, 1, 2),
    )

    def local_eval(params, batch_stats, x, y):
        logits = model.apply({"params": params, "batch_stats": batch_stats}, x)
        correct = (logits.argmax(-1) == y).sum()
        return jax.lax.psum(correct, "_ranks")

    evaluate = jax.jit(
        shard_map_fn(
            local_eval,
            mesh=mesh,
            in_specs=(P(), P(), P("_ranks"), P("_ranks")),
            out_specs=P(),
        )
    )

    xtr, ytr = synthetic_cifar(args.train_size, 0)
    xte, yte = synthetic_cifar(args.test_size, 1)

    # per-rank sampler + loader, microbatches packed rank-major (reference
    # DistributedSampler semantics over the dp world)
    samplers = [
        tdx.DistributedSampler(range(len(xtr)), num_replicas=W, rank=r, shuffle=True)
        for r in range(W)
    ]

    params, batch_stats = variables["params"], variables["batch_stats"]
    opt_state = opt.init(params)

    for epoch in range(1, args.epochs + 1):
        for s in samplers:
            s.set_epoch(epoch)
        idx_per_rank = [list(iter(s)) for s in samplers]
        steps = min(len(ix) for ix in idx_per_rank) // args.batch_size
        t0 = time.perf_counter()
        train_loss = 0.0
        for b in range(steps):
            rows = np.concatenate(
                [ix[b * args.batch_size : (b + 1) * args.batch_size] for ix in idx_per_rank]
            )
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, jnp.asarray(xtr[rows], dtype), jnp.asarray(ytr[rows])
            )
            train_loss += float(loss)
        dt = time.perf_counter() - t0

        n_eval = len(xte) // W * W
        correct = evaluate(
            params, batch_stats, jnp.asarray(xte[:n_eval], dtype), jnp.asarray(yte[:n_eval])
        )
        acc = float(correct) / n_eval
        sps = steps * args.batch_size * W / dt
        print(
            f"Epoch: {epoch}/{args.epochs}, train loss: {train_loss / max(steps,1):.4f}, "
            f"test acc: {acc * 100:.2f}%, {sps:.0f} samples/s ({sps / W:.0f}/chip)"
        )

    tdx.destroy_process_group()


if __name__ == "__main__":
    main()
