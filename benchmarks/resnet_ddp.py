"""ResNet-18 / CIFAR-10 DDP throughput — BASELINE.json config #3.

Synthetic CIFAR-shaped data (32x32x3), DDP over every visible device,
SGD+momentum, BatchNorm in train mode. Reports samples/s/chip.

Usage: python benchmarks/resnet_ddp.py [--batch 128] [--steps 50] [--bf16]
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128, help="per-chip batch")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--bf16", action="store_true")
    args = ap.parse_args()
    args.warmup = max(1, args.warmup)  # >=1: compile must precede timing

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import pytorch_distributed_example_tpu as tdx
    from pytorch_distributed_example_tpu.models import (
        ResNet18,
        convert_sync_batchnorm,
    )
    from benchmarks.common import device_sync, emit

    if not tdx.is_initialized():
        tdx.init_process_group(backend="xla")
    W = tdx.get_world_size()
    gb = args.batch * W

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    # sync BN: per-device batches normalize with GLOBAL statistics (one
    # psum per norm inside the step) — torch's DDP+SyncBatchNorm recipe
    model = convert_sync_batchnorm(
        ResNet18(num_classes=10, dtype=dtype), axis_name="_ranks"
    )
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
    opt = optax.sgd(0.1, momentum=0.9)

    # BatchNorm state makes this a (params, batch_stats) step — run it as a
    # DDP-style pmean-inside-jit program over the dp mesh
    from pytorch_distributed_example_tpu._compat import shard_map_fn
    from jax.sharding import PartitionSpec as P

    mesh = tdx.distributed._get_default_group().mesh.jax_mesh

    def local_step(params, batch_stats, opt_state, x, y):
        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": batch_stats},
                x,
                train=True,
                mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
            return loss, mut["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads = jax.tree_util.tree_map(lambda g: jax.lax.pmean(g, "_ranks"), grads)
        # batch_stats already agree across ranks (sync BN psums inside)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, jax.lax.pmean(loss, "_ranks")

    step = jax.jit(
        shard_map_fn(
            local_step,
            mesh=mesh,
            in_specs=(P(), P(), P(), P("_ranks"), P("_ranks")),
            out_specs=(P(), P(), P(), P()),
        ),
        donate_argnums=(0, 1, 2),
    )

    gen = np.random.default_rng(0)
    x = jnp.asarray(gen.standard_normal((gb, 32, 32, 3)), dtype)
    y = jnp.asarray(gen.integers(0, 10, gb), jnp.int32)

    params, batch_stats = variables["params"], variables["batch_stats"]
    opt_state = opt.init(params)
    for _ in range(args.warmup):
        params, batch_stats, opt_state, loss = step(params, batch_stats, opt_state, x, y)
    device_sync(loss)

    t0 = time.perf_counter()
    for _ in range(args.steps):
        params, batch_stats, opt_state, loss = step(params, batch_stats, opt_state, x, y)
    device_sync(loss)
    dt = time.perf_counter() - t0

    per_chip = args.steps * gb / dt / W
    emit(
        "resnet18_cifar_ddp_samples_per_sec_per_chip",
        per_chip,
        "samples/s/chip",
        world=W,
        batch_per_chip=args.batch,
        dtype=str(dtype.__name__ if hasattr(dtype, "__name__") else dtype),
        loss=round(float(loss), 4),
        platform=jax.devices()[0].platform,
        device_kind=getattr(jax.devices()[0], "device_kind", "?"),
        timing="readback_barrier",
    )


if __name__ == "__main__":
    main()
