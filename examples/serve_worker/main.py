"""Serve worker gang member — the process the elastic agent spawns.

One `ServeWorker` per gang member: builds a deterministic engine
(every rank, every generation inits the same params from --seed, so a
re-formed gang replays token-identically), connects to the agent's
store, runs the generation-entry protocol (start fault point →
leader-elected geometry restore → register), and serves the shared
ledger until the agent signals drain or the front door shuts the
plane down.

Launch under the agent (single node, elastic 1-3 workers):

    python -m pytorch_distributed_example_tpu.elastic.run \
        --standalone --nproc-per-node 2:3 --serve-drain-grace-s 5 \
        examples/serve_worker/main.py --slots 4

then drive traffic/resizes from a controller process via
`serve.worker.GangRouter` + `serve.worker.ElasticGangScaler`.

Pre-warm knobs: ``TDX_COMPILE_CACHE=<dir>`` points every incarnation
at a shared persistent compilation cache and AOT-warms the engine's
programs at startup — a post-resize engine's first token then costs a
cache read instead of a compile. ``TDX_PREWARM_DIR=<dir>`` goes
further: the first incarnation to arrive serializes its compiled
executables there, and every later incarnation (any gang width)
restores them with the engine's ``precompiled=`` knob — no re-trace,
no re-compile. ``TDX_SERVE_CPU=1``
pins a 1-device CPU backend.
"""

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--d-model", type=int, default=32)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--max-seq-len", type=int, default=32)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0,
                   help="param init seed — identical across the gang")
    p.add_argument("--poll-interval-s", type=float, default=0.005)
    p.add_argument("--cpu", action="store_true",
                   help="pin a 1-device CPU backend (CI / laptop gangs)")
    args = p.parse_args()

    if args.cpu or os.environ.get("TDX_SERVE_CPU"):
        from pytorch_distributed_example_tpu._compat import (
            force_cpu_devices,
        )

        force_cpu_devices(1)

    cache_dir = os.environ.get("TDX_COMPILE_CACHE", "")
    if cache_dir:
        # BEFORE any compile: every program this process builds lands
        # in (or loads from) the gang-shared persistent cache
        from pytorch_distributed_example_tpu.serve.prewarm import (
            enable_compile_cache,
        )

        enable_compile_cache(cache_dir)

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_example_tpu.serve.engine import ServeEngine
    from pytorch_distributed_example_tpu.serve.worker import (
        ServeWorker,
        worker_store_from_env,
    )

    rank = int(os.environ.get("RANK", "0"))
    gen = int(os.environ.get("TDX_RESTART_COUNT", "0"))

    cfg = TransformerConfig(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_layers=args.layers,
        n_heads=args.heads,
        max_seq_len=args.max_seq_len,
        use_flash=False,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(args.seed), jnp.zeros((1, 4), jnp.int32)
    )
    prewarm_dir = os.environ.get("TDX_PREWARM_DIR", "")
    precompiled = None
    if prewarm_dir:
        from pytorch_distributed_example_tpu.serve.prewarm import (
            load_precompiled,
        )

        precompiled = load_precompiled(prewarm_dir) or None
    import time

    engine = ServeEngine(
        model,
        params,
        slots=args.slots,
        temperature=args.temperature,
        precompiled=precompiled,
        # wall clock: the front door stamps arrivals with time.time
        # from ANOTHER process — TTFT/SLO math needs one timebase
        clock=time.time,
    )
    if prewarm_dir and precompiled is None:
        # first incarnation to arrive: pay the compile once, serialize
        # for every later generation at any gang width
        from pytorch_distributed_example_tpu.serve.prewarm import (
            prewarm_engine_programs,
        )

        timings = prewarm_engine_programs(engine, save_dir=prewarm_dir)
        print(
            f"[gen {gen}] rank {rank}: pre-warmed {len(timings)} "
            f"programs ({sum(timings.values()):.2f}s total)",
            flush=True,
        )
    elif cache_dir:
        from pytorch_distributed_example_tpu.serve.prewarm import (
            prewarm_engine_programs,
        )

        timings = prewarm_engine_programs(engine)
        print(
            f"[gen {gen}] rank {rank}: cache-warmed "
            f"{len(timings)} programs "
            f"({sum(timings.values()):.2f}s total)",
            flush=True,
        )

    store = worker_store_from_env()
    worker = ServeWorker(
        store,
        engine,
        rank=rank,
        gen=gen,
        poll_interval_s=args.poll_interval_s,
    ).start()
    print(
        f"[gen {gen}] rank {rank}: serving "
        f"(leader={worker.is_leader}, restored={worker.restored})",
        flush=True,
    )
    reason = worker.serve_forever()
    print(f"[gen {gen}] rank {rank}: exiting ({reason})", flush=True)


if __name__ == "__main__":
    main()
