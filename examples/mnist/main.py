"""MNIST DDP training — parity with the reference's mnist/main.py.

Reference behavior [RECONSTRUCTED, SURVEY.md §2.0 E2]: ConvNet on MNIST,
per-rank DataLoader + DistributedSampler, model wrapped in
DistributedDataParallel, SGD loop, train+eval per epoch, metrics averaged
across ranks (`Average`/`Accuracy` helpers, `Trainer.fit`).

TPU-native form: the per-rank loaders' microbatches are packed rank-major
into one global batch per step; the jitted DDP step (forward + backward +
gradient pmean + SGD update fused into one XLA program) consumes it with
batch sharded over the dp axis and params replicated. Same CLI flags as the
stock script.

Run:  python examples/mnist/main.py --epochs 2 --batch-size 64
      (uses synthetic MNIST unless --root points at IDX files)
"""

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np


class Average:
    """Running average — the reference's metric helper [RECONSTRUCTED]."""

    def __init__(self):
        self.sum = 0.0
        self.count = 0

    def update(self, value: float, number: int = 1):
        self.sum += value * number
        self.count += number

    @property
    def average(self) -> float:
        return self.sum / max(self.count, 1)

    def __str__(self):
        return f"{self.average:.6f}"


class Accuracy:
    def __init__(self):
        self.correct = 0
        self.count = 0

    def update(self, correct: int, number: int):
        self.correct += correct
        self.count += number

    @property
    def accuracy(self) -> float:
        return self.correct / max(self.count, 1)

    def __str__(self):
        return f"{self.accuracy * 100:.2f}%"


class Trainer:
    """fit/train/evaluate — the reference's Trainer [RECONSTRUCTED]."""

    def __init__(self, ddp, optimizer, train_data, test_data, batch_size,
                 world_size, rng, num_workers=0, worker_mode="thread",
                 steps_per_call=1):
        import jax
        import optax
        from pytorch_distributed_example_tpu.data import DataLoader, DistributedSampler

        self.ddp = ddp
        self.world_size = world_size
        self.batch_size = batch_size
        self.rng = rng

        def loss_fn(logits, y):
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        def metric_fn(logits, y, w):
            import jax.numpy as jnp

            ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            correct = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
            return jnp.stack([(ce * w).sum(), (correct * w).sum(), w.sum()])

        self.train_step = ddp.make_train_step(optimizer, loss_fn, has_rng=True)
        # stateful comm hooks (PowerSGD / blockwise quant error
        # feedback) thread a state pytree through the compiled step
        self.hook_state = None
        if hasattr(self.train_step, "init_hook_state"):
            self.hook_state = self.train_step.init_hook_state(ddp.params)
        # --steps-per-call K: K full optimizer steps fused (unrolled)
        # into one compiled program — identical math to K sequential
        # steps (tests/test_ddp.py pins it), host dispatch paid once per
        # K. The single-step path still handles the epoch's ragged tail.
        self.steps_per_call = steps_per_call
        if steps_per_call > 1:
            self.train_step_k = ddp.make_train_step(
                optimizer, loss_fn, has_rng=True,
                steps_per_call=steps_per_call, unroll_steps=True,
            )
        self.eval_step = ddp.make_eval_step(metric_fn)
        self.opt_state = optimizer.init(ddp.params)
        self.params = ddp.params

        # one sampler+loader per rank; microbatches packed rank-major
        self.samplers = [
            DistributedSampler(train_data, num_replicas=world_size, rank=r)
            for r in range(world_size)
        ]
        self.loaders = [
            DataLoader(train_data, batch_size, sampler=s,
                       num_workers=num_workers, worker_mode=worker_mode)
            for s in self.samplers
        ]
        self.test_data = test_data

    def fit(self, epochs: int):
        for epoch in range(1, epochs + 1):
            t0 = time.perf_counter()
            train_loss, seen = self.train(epoch)
            test_loss, test_acc = self.evaluate()
            dt = time.perf_counter() - t0
            ips = seen / dt
            print(
                f"Epoch: {epoch}/{epochs}, "
                f"train loss: {train_loss:.6f}, "
                f"test loss: {test_loss:.6f}, test acc: {test_acc*100:.2f}%, "
                f"{ips:,.0f} samples/s ({ips/self.world_size:,.0f}/chip)"
            )

    def train(self, epoch: int):
        import jax

        for s in self.samplers:
            s.set_epoch(epoch)
        avg = Average()
        seen = 0
        pending = []  # buffered global batches for the fused K-step call
        for microbatches in zip(*[iter(l) for l in self.loaders]):
            xs = np.concatenate([x for x, _ in microbatches])
            ys = np.concatenate([y for _, y in microbatches])
            if xs.shape[0] % self.world_size != 0:
                continue  # ragged tail microbatch set
            if self.steps_per_call > 1:
                pending.append((xs, ys))
                if len(pending) == self.steps_per_call:
                    seen += self._run_fused(pending, avg)
                    pending = []
                continue
            self.rng, sub = _split(self.rng)
            loss = self._run_single(xs, ys, sub)
            avg.update(float(loss), xs.shape[0])
            seen += xs.shape[0]
        for xs, ys in pending:  # ragged tail: single-step fallback
            self.rng, sub = _split(self.rng)
            loss = self._run_single(xs, ys, sub)
            avg.update(float(loss), xs.shape[0])
            seen += xs.shape[0]
        return avg.average, seen

    def _run_single(self, xs, ys, sub):
        if self.hook_state is not None:
            (
                self.params, self.opt_state, self.hook_state, loss,
            ) = self.train_step(
                self.params, self.opt_state, self.hook_state, xs, ys, sub
            )
        else:
            self.params, self.opt_state, loss = self.train_step(
                self.params, self.opt_state, xs, ys, sub
            )
        return loss

    def _run_fused(self, pending, avg):
        import jax

        K = len(pending)
        xs = np.stack([x for x, _ in pending])
        ys = np.stack([y for _, y in pending])
        self.rng, sub = _split(self.rng)
        keys = jax.random.split(sub, K)
        if self.hook_state is not None:
            (
                self.params, self.opt_state, self.hook_state, losses,
            ) = self.train_step_k(
                self.params, self.opt_state, self.hook_state, xs, ys, keys
            )
        else:
            self.params, self.opt_state, losses = self.train_step_k(
                self.params, self.opt_state, xs, ys, keys
            )
        n = sum(x.shape[0] for x, _ in pending)
        avg.update(float(np.asarray(losses).mean()), n)
        return n

    def evaluate(self):
        n = len(self.test_data)
        eb = self.batch_size * self.world_size
        # pad with wraparound indices + zero weights so every sample counts
        # exactly once regardless of n % eb
        n_pad = ((n + eb - 1) // eb) * eb
        idx_all = np.arange(n_pad) % n
        w_all = (np.arange(n_pad) < n).astype(np.float32)
        loss_sum = correct = count = 0.0
        for start in range(0, n_pad, eb):
            idx = idx_all[start : start + eb]
            x, y = self.test_data[idx]
            m = np.asarray(self.eval_step(self.params, x, y, w_all[start : start + eb]))
            loss_sum += float(m[0])
            correct += float(m[1])
            count += float(m[2])
        return loss_sum / max(count, 1), correct / max(count, 1)


def _split(rng):
    import jax

    a, b = jax.random.split(rng)
    return a, b


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--backend", type=str, default="xla")
    p.add_argument("--init-method", type=str, default="tcp://127.0.0.1:23456")
    p.add_argument("--rank", type=int, default=0)
    p.add_argument("--world-size", type=int, default=-1)
    p.add_argument("--epochs", type=int, default=2)
    p.add_argument("--lr", type=float, default=0.01)
    p.add_argument("--momentum", type=float, default=0.5)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--root", type=str, default=None, help="MNIST IDX data dir")
    p.add_argument("--num-workers", type=int, default=0,
                   help="loader workers per rank (the reference CLI's flag)")
    p.add_argument("--worker-mode", choices=["thread", "process"],
                   default="thread",
                   help="process = torch-style worker processes with a "
                        "shared-memory return path (GIL-bound decode)")
    p.add_argument("--steps-per-call", type=int, default=1,
                   help="fuse K full optimizer steps into one compiled "
                        "program (math identical "
                        "to K sequential steps)")
    p.add_argument("--quant-hook", action="store_true",
                   help="all-reduce gradients through the blockwise "
                   "int8 wire-quantized hook with error feedback "
                   "(parallel.blockwise_quant_hook)")
    p.add_argument("--cpu", action="store_true",
                   help="run on a virtual 2-device CPU mesh")
    args = p.parse_args()

    import jax

    if args.cpu or __import__("os").environ.get("TDX_EXAMPLES_CPU"):
        from pytorch_distributed_example_tpu._compat import force_cpu_devices

        force_cpu_devices(2)
    from pytorch_distributed_example_tpu._compat import enable_compile_cache

    enable_compile_cache()
    import jax.numpy as jnp
    import optax

    import pytorch_distributed_example_tpu as tdx
    from pytorch_distributed_example_tpu.data import load_mnist
    from pytorch_distributed_example_tpu.models import ConvNet

    tdx.init_process_group(backend=args.backend, world_size=args.world_size, rank=args.rank)
    world = tdx.get_world_size()
    print(f"backend={tdx.get_backend()} world_size={world} devices={jax.devices()[:world]}")

    train_data = load_mnist(args.root, train=True)
    test_data = load_mnist(args.root, train=False)

    model = ConvNet()
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, 28, 28, 1)))
    ddp = tdx.DistributedDataParallel(model, params)
    if args.quant_hook:
        from pytorch_distributed_example_tpu.parallel import (
            blockwise_quant_hook,
        )

        ddp.register_comm_hook(None, blockwise_quant_hook(bits=8))
    optimizer = optax.sgd(args.lr, momentum=args.momentum)

    trainer = Trainer(ddp, optimizer, train_data, test_data,
                      args.batch_size, world, rng,
                      num_workers=args.num_workers,
                      worker_mode=args.worker_mode,
                      steps_per_call=args.steps_per_call)
    trainer.fit(args.epochs)
    tdx.destroy_process_group()


if __name__ == "__main__":
    main()
