"""Flash-attention block-size sweep + dense comparison (TPU tuning tool).

Times the Pallas flash kernel (fwd and fwd+bwd) across (block_q,
block_k) candidates at a given geometry, against the dense reference —
run on real hardware to pick `TDX_FLASH_BLOCK_Q/K`. Emits one JSON line
with the full table and the best configuration.

Usage: python benchmarks/flash_bench.py [--seq 2048] [--batch 4]
    [--heads 8] [--dh 128] [--causal] [--bf16]
"""

from __future__ import annotations

import argparse
import itertools
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dh", type=int, default=128)
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument(
        "--blocks", default="128,256,512",
        help="comma-separated candidate block sizes",
    )
    ap.add_argument(
        "--skip-dense", action="store_true",
        help="skip the dense-attention comparison (long sequences: the "
             "dense L^2 score matrix OOMs exactly where flash shines)",
    )
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import device_sync, emit
    from pytorch_distributed_example_tpu.ops import flash_attention
    from pytorch_distributed_example_tpu.ops.reference import dense_attention

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    gen = np.random.default_rng(0)
    shape = (args.batch, args.seq, args.heads, args.dh)
    q = jnp.asarray(gen.standard_normal(shape), dtype)
    k = jnp.asarray(gen.standard_normal(shape), dtype)
    v = jnp.asarray(gen.standard_normal(shape), dtype)

    def timed(fn_one):
        # `fn_one: (q, k, v) -> q-shaped array`. A dispatch costs more
        # than these kernels run, so iterations chain inside ONE jitted
        # lax.scan program; k/v are explicit ARGUMENTS, not closure
        # captures — captured arrays embed as HLO constants and bloat the
        # program at long sequences.
        @jax.jit
        def chained(x, kk, vv):
            def body(c, _):
                return fn_one(c, kk, vv).astype(x.dtype), None
            c, _ = jax.lax.scan(body, x, None, length=args.iters)
            return c
        device_sync(chained(q, k, v))  # drain compile + first execution
        t0 = time.perf_counter()
        device_sync(chained(q, k, v))
        return (time.perf_counter() - t0) / args.iters * 1e3  # ms

    cands = [int(b) for b in args.blocks.split(",") if args.seq % int(b) == 0]
    table = {}
    for bq, bk in itertools.product(cands, cands):
        def fwd_one(x, kk, vv, bq=bq, bk=bk):
            return flash_attention(
                x, kk, vv, causal=args.causal, block_q=bq, block_k=bk
            )

        def bwd_one(x, kk, vv, bq=bq, bk=bk):
            return jax.grad(
                lambda xx: flash_attention(
                    xx, kk, vv, causal=args.causal, block_q=bq, block_k=bk
                ).astype(jnp.float32).sum()
            )(x)

        try:
            table[f"{bq}x{bk}"] = {
                "fwd_ms": round(timed(fwd_one), 3),
                "fwd_bwd_ms": round(timed(bwd_one), 3),
            }
        except Exception as e:  # VMEM overflow etc.: record, keep sweeping
            table[f"{bq}x{bk}"] = {"error": f"{type(e).__name__}"}

    if args.skip_dense:
        dense_ms = None  # skipped, not measured-zero
    else:
        dense_ms = round(
            timed(
                lambda x, kk, vv: dense_attention(
                    x, kk, vv, causal=args.causal
                )
            ),
            3,
        )

    ok = {k: v for k, v in table.items() if "fwd_ms" in v}
    best_fwd = min(ok, key=lambda k: ok[k]["fwd_ms"]) if ok else None
    best_train = min(ok, key=lambda k: ok[k]["fwd_bwd_ms"]) if ok else None
    rec = emit(
        "flash_attention_best_fwd_ms",
        ok[best_fwd]["fwd_ms"] if best_fwd else 0.0,
        "ms",
        best_fwd_blocks=best_fwd,
        best_train_blocks=best_train,  # may differ: pick per workload
        best_train_fwd_bwd_ms=ok[best_train]["fwd_bwd_ms"] if best_train else 0.0,
        dense_fwd_ms=dense_ms,
        dense_skipped=args.skip_dense,
        speedup_vs_dense=(
            round(dense_ms / ok[best_fwd]["fwd_ms"], 2)
            if (best_fwd and dense_ms) else None
        ),
        table=table,
        seq=args.seq,
        heads=args.heads,
        dh=args.dh,
        causal=args.causal,
        dtype=str(jnp.dtype(dtype).name),
        iters=args.iters,
        timing="scan_chained",
    )
    from benchmarks.common import on_tpu, persist_result

    # keep the sweep's rows with the run's other results — but only a
    # sweep that actually produced a winner may overwrite prior evidence,
    # and sweeps at different geometries keep separate keys
    if on_tpu() and best_fwd is not None:
        persist_result(
            f"flash_sweep_L{args.seq}_dh{args.dh}"
            + ("_causal" if args.causal else ""),
            rec,
        )


if __name__ == "__main__":
    main()
