"""Toy collective example — parity with the reference's toy/main.py.

Reference behavior [RECONSTRUCTED, SURVEY.md §2.0 E1]: each rank makes a
scalar tensor holding its rank, all_reduce(SUM) over a group of all ranks,
prints the reduced value each step.

TPU-native form: one driver process owns every rank (device); per-rank
values live in a DistTensor (one shard per device) and the all_reduce is a
compiled psum over the ICI mesh. The stock CLI flags are kept
(`--backend`, `--init-method`, `--rank`, `--world-size`) so the launch
recipe from the reference README still works — `--backend gloo` aliases to
the XLA backend.

Run:  python examples/toy/main.py --world-size 8 --steps 5
"""

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np

import pytorch_distributed_example_tpu as tdx
from pytorch_distributed_example_tpu.types import ReduceOp


def run(world_size: int, steps: int) -> None:
    group = tdx.new_group(range(world_size)) if world_size < tdx.get_world_size() else None
    for step in range(steps):
        t = tdx.DistTensor.from_rank_fn(
            lambda r: np.array([float(r + step)], dtype=np.float32), group
        )
        tdx.all_reduce(t, ReduceOp.SUM, group)
        vals = [v.item() for v in t.unstack()]
        expect = sum(r + step for r in range(world_size))
        print(f"step {step}: all_reduce(SUM) -> {vals[0]} (every rank agrees: "
              f"{all(v == vals[0] for v in vals)}, expect {expect})")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--backend", type=str, default="xla")
    p.add_argument("--init-method", type=str, default="tcp://127.0.0.1:23456")
    p.add_argument("--rank", type=int, default=0)
    p.add_argument("--world-size", type=int, default=-1)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--cpu", action="store_true",
                   help="run on a virtual 8-device CPU mesh")
    p.add_argument("--schedule-check", action="store_true",
                   help="arm the cross-rank collective-schedule verifier "
                        "(TDX_SCHEDULE_CHECK=1): every collective is "
                        "fingerprinted and divergent schedules raise a "
                        "diagnostic naming the offending op instead of "
                        "hanging")
    args = p.parse_args()

    import os
    if args.schedule_check:
        # must be set before init_process_group: the verifier is armed at
        # group creation
        os.environ["TDX_SCHEDULE_CHECK"] = "1"
    if args.cpu or os.environ.get("TDX_EXAMPLES_CPU"):
        from pytorch_distributed_example_tpu._compat import force_cpu_devices

        force_cpu_devices(8)
    from pytorch_distributed_example_tpu._compat import enable_compile_cache

    enable_compile_cache()

    tdx.init_process_group(
        backend=args.backend,
        world_size=args.world_size,
        rank=args.rank,
    )
    ws = tdx.get_world_size()
    print(f"initialized: backend={tdx.get_backend()} world_size={ws}")
    run(ws if args.world_size == -1 else args.world_size, args.steps)
    tdx.destroy_process_group()


if __name__ == "__main__":
    main()
