"""Elastic DDP training — resume across dynamic world sizes.

The torchelastic canonical workflow (torch `run.py` docs: workers must
tolerate restarts and re-rendezvous at a different world size): every
generation, workers load the latest checkpoint, train to the target
step count, and checkpoint periodically; a worker loss re-forms the
gang (fewer ranks, same global batch semantics via per-rank batch) and
training CONTINUES from the last checkpoint instead of restarting.

Launch (single node, gang elastic between 2 and 4 workers):

    python -m pytorch_distributed_example_tpu.elastic.run \
        --standalone --nproc-per-node 2:4 \
        examples/elastic/main.py --steps 200 --ckpt /tmp/elastic_ckpt

Multi-node (node-level elasticity, 1-2 agents):

    python -m pytorch_distributed_example_tpu.elastic.run \
        --nnodes 1:2 --node-rank 0 --rdzv-endpoint HOST:29500 \
        examples/elastic/main.py --steps 200

While it runs, `pytorch_distributed_example_tpu.elastic.request_join`
against the agent's join endpoint grows the gang at the next boundary.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=120, help="TOTAL step target")
    p.add_argument("--ckpt-every", type=int, default=20)
    p.add_argument("--ckpt", default="/tmp/tdx_elastic_ckpt")
    p.add_argument("--batch-size", type=int, default=32, help="per rank")
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--cpu", action="store_true",
                   help="pin a 1-device CPU backend (CI / laptop gangs)")
    args = p.parse_args()

    import jax

    if args.cpu or os.environ.get("TDX_ELASTIC_CPU"):
        from pytorch_distributed_example_tpu._compat import force_cpu_devices

        force_cpu_devices(1)
    from pytorch_distributed_example_tpu._compat import enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp
    import numpy as np
    import optax

    import pytorch_distributed_example_tpu as tdx
    from pytorch_distributed_example_tpu import checkpoint
    from pytorch_distributed_example_tpu.models import ConvNet

    tdx.init_process_group(backend="xla", init_method="env://")
    rank, world = tdx.get_rank(), tdx.get_world_size()
    gen = os.environ.get("TDX_RESTART_COUNT", "0")

    model = ConvNet()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))
    # DDP wrap AFTER load decisions: the broadcast makes every rank
    # identical even if only some ranks saw the checkpoint files
    opt = optax.sgd(args.lr, momentum=0.9)

    start_step = 0
    if os.path.isdir(args.ckpt):
        try:
            params, _, start_step, _ = checkpoint.load_checkpoint(
                args.ckpt, params
            )
        except Exception as e:  # fresh run or torn write: start over
            print(f"[rank {rank}] checkpoint ignored: {e}", flush=True)

    ddp = tdx.DistributedDataParallel(model, params)
    step_fn = ddp.make_train_step(
        opt,
        lambda lg, y: optax.softmax_cross_entropy_with_integer_labels(
            lg, y
        ).mean(),
    )
    opt_state = opt.init(ddp.params)

    # synthetic per-rank data (elastic semantics: per-RANK batch is fixed,
    # the global batch scales with the surviving world size — torch DDP
    # under torchelastic behaves the same way)
    gen_rng = np.random.default_rng(1234 + rank)
    x = gen_rng.standard_normal(
        (args.batch_size * world, 28, 28, 1)
    ).astype(np.float32)
    y = gen_rng.integers(0, 10, args.batch_size * world).astype(np.int32)

    print(
        f"[gen {gen}] rank {rank}/{world}: resuming at step {start_step}",
        flush=True,
    )
    params_t, loss = ddp.params, None
    for step in range(start_step, args.steps):
        params_t, opt_state, loss = step_fn(params_t, opt_state, x, y)
        done = step + 1
        if done % args.ckpt_every == 0 or done == args.steps:
            if rank == 0:
                checkpoint.save_checkpoint(
                    args.ckpt, params_t, step=done
                )
            tdx.barrier()  # nobody races past a torn checkpoint
    # a restart can land AFTER the final checkpoint: the resumed
    # generation then has nothing left to run — exit 0, not a crash
    loss_txt = (
        f"{float(np.asarray(jax.device_get(loss))):.4f}"
        if loss is not None
        else "n/a (already complete at resume)"
    )
    print(
        f"[gen {gen}] rank {rank}/{world}: reached step {args.steps}, "
        f"final loss {loss_txt}",
        flush=True,
    )
    tdx.destroy_process_group()


if __name__ == "__main__":
    main()
