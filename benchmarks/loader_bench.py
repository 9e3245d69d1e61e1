"""DataLoader worker-scaling bench — where the THREAD model saturates.

Round-2 VERDICT weak #5: `data/loader.py` uses a thread pool (not
torch's worker processes), justified for numpy-gather workloads (GIL
released inside numpy) but expected to serialize on GIL-bound python
decode. This bench commits the numbers for both regimes across worker
counts, so the thread-model tradeoff is on record rather than asserted:

* ``numpy``  — slicing + normalizing a preallocated array (C-level,
  GIL released): threads should scale.
* ``decode`` — a deliberately python-heavy per-sample transform
  (bytes -> int loops), the shape of real python-side decode: threads
  cannot scale past ~1.3x. ``worker_mode="process"`` (round-3 VERDICT
  #4: torch's worker-process design with a shared-memory return path)
  is the fix — this bench sweeps both modes so the crossover is on
  record.

Usage: python benchmarks/loader_bench.py [--batches 40] [--batch 64]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


class _NumpyDataset:
    """GIL-releasing workload: fancy-index + fp32 normalize."""

    def __init__(self, n=8192, dim=3072):
        import numpy as np

        self.x = np.random.default_rng(0).integers(
            0, 255, (n, dim), dtype=np.uint8
        )

    def __len__(self):
        return len(self.x)

    def __getitem__(self, idx):
        import numpy as np

        batch = self.x[idx].astype(np.float32)
        return (batch / 127.5 - 1.0), np.zeros(len(idx), np.int32)


class _PyDecodeDataset:
    """GIL-bound workload: per-sample python byte loops (decode-shaped)."""

    def __init__(self, n=8192, blob=4096):
        self.blobs = [bytes(range(256)) * (blob // 256) for _ in range(n)]

    def __len__(self):
        return len(self.blobs)

    def __getitem__(self, idx):
        import numpy as np

        out = []
        for i in idx:
            acc = 0
            for b in self.blobs[i]:  # pure-python per-byte work
                acc = (acc + b) & 0xFFFF
            out.append(acc)
        return np.asarray(out, np.float32), np.zeros(len(idx), np.int32)


class _IoDataset:
    """IO-wait workload (network/disk-shaped): per-batch blocking wait +
    a small gather. Scales with workers in EITHER model regardless of
    host core count — isolates the loader's dispatch pipeline from the
    host's compute parallelism (this repo's bench box has 1 core, which
    caps CPU-bound scaling at ~1x for every worker model)."""

    def __init__(self, n=8192, wait_s=0.01):
        import numpy as np

        self.wait_s = wait_s
        self.x = np.zeros((n, 16), np.float32)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, idx):
        import time

        import numpy as np

        time.sleep(self.wait_s)  # the IO stall prefetch exists to hide
        return self.x[idx], np.zeros(len(idx), np.int32)


def _throughput(loader, batches, step_s=0.0):
    """samples/s draining the loader, optionally simulating a consumer
    train step of `step_s` per batch — prefetch exists to hide fetch
    UNDER the step, so the step_s>0 row is the loader's real job.

    The clock covers iterator creation through the last batch: starting
    it after a warm-up `next()` would let the pool bank up to
    num_workers finished batches outside the window, inflating
    multi-worker rows (especially at small --batches)."""
    t0 = time.perf_counter()
    n = 0
    for i, (x, y) in enumerate(loader):
        n += len(x)
        if step_s:
            time.sleep(step_s)
        if i + 1 >= batches:
            break
    return n / (time.perf_counter() - t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", type=int, default=40)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--workers", default="0,2,4,8")
    ap.add_argument("--step-ms", type=float, default=5.0,
                    help="simulated consumer train-step per batch; 0 = "
                         "pure drain (measures dispatch overhead only)")
    ap.add_argument("--modes", default="thread,process",
                    help="worker models to sweep (round-3 VERDICT #4: "
                         "process workers escape the decode GIL ceiling)")
    args = ap.parse_args()

    from benchmarks.common import emit
    from pytorch_distributed_example_tpu.data import DataLoader

    step_s = args.step_ms / 1e3
    workers = [int(x) for x in args.workers.split(",")]
    results = []
    host_cpus = len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") \
        else (os.cpu_count() or 1)
    for name, ds in (
        ("numpy", _NumpyDataset()),
        ("decode", _PyDecodeDataset()),
        ("io", _IoDataset()),
    ):
        base = None
        base_key = None
        for mode in args.modes.split(","):
            for w in workers:
                if w == 0 and mode == "process":
                    continue  # w=0 is the same inline path in both modes
                loader = DataLoader(
                    ds,
                    batch_size=args.batch,
                    num_workers=w,
                    shuffle=False,
                    worker_mode=mode if w else "thread",
                )
                sps = _throughput(loader, args.batches, step_s)
                loader.shutdown()
                this_key = f"{mode}_w{w}" if mode == "process" else f"w{w}"
                if base is None:
                    # labeled by the config that ACTUALLY ran first — a
                    # --modes/--workers subset must not mislabel its
                    # self-relative baseline as "vs w0"
                    base, base_key = sps, this_key
                tagged = f"loader_{name}_{this_key}"
                rec = emit(
                    tagged,
                    round(sps, 1),
                    "samples/s",
                    workers=w,
                    worker_mode=mode if w else "inline",
                    step_ms=args.step_ms,
                    **{f"speedup_vs_{base_key}": round(sps / base, 2)},
                )
                results.append(rec)
    emit(
        "loader_scaling_summary",
        len(results),
        "rows",
        host_cpus=host_cpus,
        caveat=(
            f"host has {host_cpus} core(s): CPU-bound workloads (numpy, "
            "decode) cannot scale past ~1x on this host in ANY worker "
            "model; the io rows isolate the dispatch pipeline, which is "
            "what transfers to multi-core hosts"
        ) if host_cpus <= 2 else None,
        rows=results,
    )
    return results


if __name__ == "__main__":
    main()
