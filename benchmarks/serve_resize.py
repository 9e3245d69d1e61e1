"""serve_resize — decision-to-first-token at a NEW gang width,
pre-warmed vs cold (ISSUE 16 tentpole row).

A process-level resize tears the gang down and respawns it; the store
half of the lifecycle (drain, seal, re-register) costs milliseconds,
so what the first post-resize token actually waits on is the NEW
engine process compiling its paged programs. `serve/prewarm.py`
pre-compiles the reachable program set into JAX's persistent
compilation cache, turning that compile into a disk read.

This bench measures exactly that seam, honestly: each sample is a
FRESH python subprocess (cold in-memory jit caches, like a respawned
worker) that builds an engine and serves one probe request to its
first emitted token:

* **cold** — empty compilation-cache directory: the price an unwarmed
  resize pays today.
* **prewarm** — the same measurement against a pre-warm directory
  populated by a prior (untimed, off-path) `prewarm_engine_programs`
  pass: the persistent compilation cache PLUS the serialized
  executables that `load_precompiled` hands the engine's
  ``precompiled=`` knob — the price after this PR, amortizable at
  deploy time or between autoscaler decisions.

The measured window opens at engine CONSTRUCTION (the moment a
respawned worker starts building its serving state — interpreter/jax
import cost is identical in both arms and reported separately) and
closes at the probe's first token (`Completion.ttft_s` on the
engine's own clock). The headline is the ratio; the acceptance bar is
``>= 5x``. Registered in benchmarks/run_all.py (quick + full); on TPU
the record self-persists into chiprun_out/bench_results.json.

Caveat (PR 21): the cold and warm arms differ only in the cache
DIRECTORY each child is handed, and every cache directory now goes
through `_compat.enable_compile_cache`, where a machine-level
`JAX_COMPILATION_CACHE_DIR` wins. On a machine that pins that variable
both arms share one cache and the comparison means nothing — run it with
the variable unset. Whether this row survives is ROADMAP S1's call.

Usage: python benchmarks/serve_resize.py [--reps 2] [--slots 4]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _child(args) -> None:
    """One measurement sample, in a fresh process: optionally attach
    the persistent cache, build the engine, serve a 2-token probe
    (first token + one paged step — the whole program quadruple), and
    print the timing JSON."""
    if args.cache_dir:
        from pytorch_distributed_example_tpu.serve.prewarm import (
            enable_compile_cache,
        )

        enable_compile_cache(args.cache_dir)
    precompiled = None
    if args.exe_dir and not args.prewarm_only:
        from pytorch_distributed_example_tpu.serve.prewarm import (
            load_precompiled,
        )

        precompiled = load_precompiled(args.exe_dir)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_example_tpu.serve.engine import ServeEngine

    cfg = TransformerConfig(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_layers=args.layers,
        n_heads=args.heads,
        max_seq_len=args.max_seq_len,
        use_flash=False,
    )
    model = TransformerLM(cfg)
    # params init (and its compile) happens in BOTH arms before the
    # window opens — a respawned worker pays it regardless of warmth
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
    )
    jax.block_until_ready(params)

    t0 = time.perf_counter()
    engine = ServeEngine(
        model,
        params,
        slots=args.slots,
        clock=time.perf_counter,
        precompiled=precompiled,
    )
    if args.prewarm_only:
        from pytorch_distributed_example_tpu.serve.prewarm import (
            prewarm_engine_programs,
        )

        timings = prewarm_engine_programs(
            engine,
            cache_dir=args.cache_dir or None,
            save_dir=args.exe_dir or None,
        )
        print(
            json.dumps(
                {
                    "prewarm_programs": len(timings),
                    "prewarm_compile_s": round(sum(timings.values()), 4),
                }
            )
        )
        return
    prompt = np.arange(1, 9, dtype=np.int32) % args.vocab
    t_submit = time.perf_counter()
    engine.submit(prompt, 2, rid="probe", seed=0)
    while engine.step():
        pass
    comp = engine.completions["probe"]
    print(
        json.dumps(
            {
                "decision_to_first_token_s": round(
                    (t_submit - t0) + comp.ttft_s, 4
                ),
                "construct_s": round(t_submit - t0, 4),
                "ttft_s": round(comp.ttft_s, 4),
                "e2e_s": round((t_submit - t0) + comp.e2e_s, 4),
            }
        )
    )


def _run_child(extra, cache_dir, exe_dir=""):
    argv = [sys.executable, os.path.abspath(__file__), "--child"] + extra
    if cache_dir:
        argv += ["--cache-dir", cache_dir]
    if exe_dir:
        argv += ["--exe-dir", exe_dir]
    out = subprocess.run(
        argv,
        capture_output=True,
        text=True,
        timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"sample process failed rc={out.returncode}:\n{out.stderr[-2000:]}"
        )
    last = [ln for ln in out.stdout.splitlines() if ln.startswith("{")][-1]
    return json.loads(last)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=2,
                    help="fresh-process samples per arm (min is reported)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=32)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=32)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--prewarm-only", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--cache-dir", default="", help=argparse.SUPPRESS)
    ap.add_argument("--exe-dir", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        _child(args)
        return

    from benchmarks.common import emit, on_tpu, persist_result

    dims = [
        "--slots", str(args.slots), "--vocab", str(args.vocab),
        "--d-model", str(args.d_model), "--layers", str(args.layers),
        "--heads", str(args.heads), "--max-seq-len", str(args.max_seq_len),
    ]
    with tempfile.TemporaryDirectory(prefix="serve-resize-") as tmp:
        warm_dir = os.path.join(tmp, "warm")
        exe_dir = os.path.join(tmp, "exe")
        os.makedirs(warm_dir)
        # populate the warm cache + serialized executables OFF the
        # measured path (deploy-time / between-decisions work)
        warm_prep = _run_child(
            dims + ["--prewarm-only"], warm_dir, exe_dir
        )
        cold, warm = [], []
        for i in range(max(args.reps, 1)):
            # every cold sample gets its OWN empty cache dir — nothing
            # the previous sample compiled may leak forward
            cold_dir = os.path.join(tmp, f"cold{i}")
            os.makedirs(cold_dir)
            cold.append(_run_child(dims, cold_dir))
            warm.append(_run_child(dims, warm_dir, exe_dir))
    cold_s = min(r["decision_to_first_token_s"] for r in cold)
    warm_s = min(r["decision_to_first_token_s"] for r in warm)
    speedup = cold_s / warm_s if warm_s > 0 else float("inf")

    import jax

    rec = emit(
        "serve_resize_first_token_speedup",
        round(speedup, 2),
        "x",
        target_x=5.0,
        decision_to_first_token_cold_s=cold_s,
        decision_to_first_token_prewarm_s=warm_s,
        construct_cold_s=min(r["construct_s"] for r in cold),
        construct_prewarm_s=min(r["construct_s"] for r in warm),
        ttft_cold_s=min(r["ttft_s"] for r in cold),
        ttft_prewarm_s=min(r["ttft_s"] for r in warm),
        prewarm_compile_s=warm_prep["prewarm_compile_s"],
        prewarm_programs=warm_prep["prewarm_programs"],
        reps=args.reps,
        slots=args.slots,
        d_model=args.d_model,
        n_layers=args.layers,
        evidence="fresh_process_per_sample",
        platform=jax.devices()[0].platform,
        device_kind=getattr(jax.devices()[0], "device_kind", "?"),
    )
    if on_tpu():
        persist_result("serve_resize", rec)


if __name__ == "__main__":
    main()
