"""Profiler-trace evidence — SURVEY.md §5.1 tier 3, round-2 VERDICT #7.

Wraps N DDP train steps in `jax.profiler.trace`, saves the trace
artifact, and ASSERTS that collective ops landed on the device timeline
— the analog of torch's `record_function("DistributedDataParallel.
forward")` blocks appearing in torch profiler traces
(`nn/parallel/distributed.py:1885`).

The check reads the generated `.xplane.pb` files and scans for XLA
collective op names (`all-reduce` / `all-gather` / `collective-permute`
...). Xplane protos embed HLO op names as plain strings, so a substring
scan is a dependency-free assertion that the collectives are ON the
timeline, not just in the program.

The durable record is the emitted JSON (run_all persists it beside the
other rows); the trace itself lands under `chiprun_out/` (MB-scale,
git-ignored — what a chip run brings back).

Usage: python benchmarks/trace_evidence.py [--out chiprun_out/traces]
Emits: {"metric": "trace_evidence", "value": 1.0, ...} on success.
"""

from __future__ import annotations

import argparse
import glob
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

COLLECTIVE_MARKERS = (
    b"all-reduce",
    b"all-gather",
    b"reduce-scatter",
    b"collective-permute",
    b"all-to-all",
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/traces")
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import pytorch_distributed_example_tpu as tdx
    from benchmarks.common import device_sync, emit
    from pytorch_distributed_example_tpu.models import ConvNet

    if not tdx.is_initialized():
        tdx.init_process_group(backend="xla")
    world = tdx.get_world_size()

    model = ConvNet()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))
    ddp = tdx.DistributedDataParallel(model, params)
    opt = optax.sgd(0.01)
    step = ddp.make_train_step(
        opt,
        lambda lg, y: optax.softmax_cross_entropy_with_integer_labels(lg, y).mean(),
    )
    opt_state = opt.init(ddp.params)
    gen = np.random.default_rng(0)
    x = gen.standard_normal((64 * world, 28, 28, 1)).astype(np.float32)
    y = gen.integers(0, 10, 64 * world).astype(np.int32)

    p = ddp.params
    p, opt_state, loss = step(p, opt_state, x, y)  # compile outside trace
    device_sync(loss)

    run_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        args.out,
        time.strftime("%Y%m%dT%H%M%S"),
    )
    with jax.profiler.trace(run_dir):
        for _ in range(args.steps):
            p, opt_state, loss = step(p, opt_state, x, y)
        device_sync(loss)  # ensure the traced steps really executed

    planes = glob.glob(
        os.path.join(run_dir, "**", "*.xplane.pb"), recursive=True
    )
    found: dict = {}
    for path in planes:
        with open(path, "rb") as f:
            blob = f.read()
        for m in COLLECTIVE_MARKERS:
            if m in blob:
                found[m.decode()] = True
    ok = bool(planes) and bool(found)
    emit(
        "trace_evidence",
        1.0 if ok else 0.0,
        "ok",
        trace_dir=os.path.relpath(run_dir),
        xplane_files=len(planes),
        collectives_on_timeline=sorted(found),
        world=world,
        platform=jax.devices()[0].platform,
    )
    if not ok:
        raise SystemExit(
            f"no collective ops found on the device timeline "
            f"({len(planes)} xplane files in {run_dir})"
        )


if __name__ == "__main__":
    main()
