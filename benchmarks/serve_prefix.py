"""Prefix-sharing serve benchmark — shared-preamble TTFT and pool
bytes, sharing ON vs OFF (ISSUE 12, ROADMAP item 2).

The trace is the millions-of-users shape the radix prefix cache
exists for: every request is ``<shared preamble> + <unique suffix>``
(one system prompt / few-shot preamble serving a whole tenant). A
WARM request populates the index outside the timed window (the steady
state of a production engine — its system prompt is always resident),
then the timed burst replays twice on identical hardware/traffic:

* **off** — `ServeEngine(prefix_cache=False)`: every request
  re-prefills and re-stores the full preamble (the PR 6 baseline).
* **on** — `ServeEngine(prefix_cache=True)`: admission attaches the
  preamble's blocks from the radix index and chunked prefill starts at
  the first uncached position, so per-request prefill work (and pool
  writes) drop from preamble+suffix to suffix only.

Figures of merit: **TTFT improvement** (mean + p50/p99, target >= 3x
on the shared-preamble trace), **pool bytes per live request** (the
paged pool's memory figure — shared preamble blocks count ONCE, so
mean live bytes/request falls vs off), and the prefix_cache metrics
block (hit rate, tokens reused, CoW copies, bytes deduplicated).
Token identity between the two replays is ASSERTED — sharing must
never change what gets served (greedy; per-request seeds make the
same assertion meaningful for sampled runs).

Usage: python benchmarks/serve_prefix.py [--preset tiny|small|base]
    [--requests 24] [--slots 8] [--preamble-tokens 96] [--seed 0]
    [--prefill-chunk 32] [--kv-quant] [--bf16]

Registered in benchmarks/run_all.py (quick + full); on TPU the record
self-persists into chiprun_out/bench_results.json like every serve row.
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

PRESETS = {
    "tiny": dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4),
    "small": dict(vocab_size=32000, d_model=256, n_layers=4, n_heads=8),
    "base": dict(vocab_size=32000, d_model=768, n_layers=12, n_heads=12),
}

SUFFIX = (8, 17)  # unique per-request tail tokens (half-open)
NEW = (8, 17)  # decode budgets — short answers, prefill-dominated


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="small")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument(
        "--preamble-tokens", type=int, default=96,
        help="shared system-prompt length every request carries",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--bf16", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit, on_tpu, persist_result
    from pytorch_distributed_example_tpu.serve import ServeEngine
    from pytorch_distributed_example_tpu.serve.metrics import percentile

    pre_n = args.preamble_tokens
    max_seq = pre_n + SUFFIX[1] + NEW[1] + 2
    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
    )

    cfg = TransformerConfig(
        max_seq_len=max_seq,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        use_flash=False,
        **PRESETS[args.preset],
    )
    model = TransformerLM(cfg)
    gen = np.random.default_rng(args.seed)
    params = model.init(
        jax.random.PRNGKey(0),
        jnp.asarray(gen.integers(0, cfg.vocab_size, (1, 8)), jnp.int32),
    )

    preamble = gen.integers(0, cfg.vocab_size, (pre_n,)).astype(np.int32)
    n = args.requests
    suffixes = [
        gen.integers(
            0, cfg.vocab_size, (int(gen.integers(*SUFFIX)),)
        ).astype(np.int32)
        for _ in range(n)
    ]
    prompts = [np.concatenate([preamble, s]) for s in suffixes]
    budgets = [int(gen.integers(*NEW)) for _ in range(n)]
    # warm set: one cold request populates the index; two followers
    # with different suffix lengths exercise the ATTACH path (the
    # post-attach prefill chunks hit shorter bucket shapes than any
    # cold prefill, and the CoW copy program) so both replays enter the
    # timed window fully compiled
    warm_prompts = [
        np.concatenate(
            [preamble, gen.integers(0, cfg.vocab_size, (k,)).astype(
                np.int32
            )]
        )
        for k in (4, SUFFIX[0] - 1, SUFFIX[1] - 1)
    ]

    def replay(prefix_on):
        """One timed burst replay. The warm set runs OUTSIDE the timed
        window in BOTH modes (it touches every compile, attach path
        included); with sharing on it additionally leaves the preamble
        resident in the index — the production steady state this bench
        models."""
        eng = ServeEngine(
            model, params, slots=args.slots, min_bucket=8,
            prefill_chunk_tokens=args.prefill_chunk,
            kv_quant=args.kv_quant, prefix_cache=prefix_on,
            clock=time.perf_counter,
        )
        for j, wp in enumerate(warm_prompts):
            eng.submit(wp, 2, rid=f"warm{j}")
            eng.run(max_steps=400 * n)
        t0 = time.perf_counter()
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            eng.submit(p, m, rid=f"r{i}", seed=i, arrival_time=t0)
        while eng.step():
            pass
        makespan = time.perf_counter() - t0
        assert eng.metrics.completed == n + len(warm_prompts)
        toks = [eng.completions[f"r{i}"].tokens for i in range(n)]
        ttft = [eng.completions[f"r{i}"].ttft_s for i in range(n)]
        return eng, toks, ttft, makespan

    eng_off, toks_off, ttft_off, span_off = replay(False)
    eng_on, toks_on, ttft_on, span_on = replay(True)
    assert toks_on == toks_off, (
        "prefix sharing changed served tokens — CoW/attach bug"
    )

    snap_on = eng_on.metrics.snapshot()
    snap_off = eng_off.metrics.snapshot()
    pc = snap_on["prefix_cache"]
    bpr_on = snap_on["cache_pool"]["bytes_per_live_request_mean"]
    bpr_off = snap_off["cache_pool"]["bytes_per_live_request_mean"]
    mean_on = sum(ttft_on) / n
    mean_off = sum(ttft_off) / n
    useful = sum(budgets)
    rec = emit(
        "serve_prefix_ttft_improvement_x",
        mean_off / max(mean_on, 1e-9),
        "x",
        target_improvement_x=3.0,
        ttft_mean_off_ms=round(mean_off * 1e3, 3),
        ttft_mean_on_ms=round(mean_on * 1e3, 3),
        ttft_p50_off_ms=round(percentile(ttft_off, 50) * 1e3, 3),
        ttft_p50_on_ms=round(percentile(ttft_on, 50) * 1e3, 3),
        ttft_p99_off_ms=round(percentile(ttft_off, 99) * 1e3, 3),
        ttft_p99_on_ms=round(percentile(ttft_on, 99) * 1e3, 3),
        ttft_p99_improvement_x=round(
            percentile(ttft_off, 99) / max(percentile(ttft_on, 99), 1e-9),
            3,
        ),
        token_identical=True,
        # pool memory: shared preamble blocks count once, so mean live
        # bytes per in-flight request FALLS vs the no-sharing replay
        pool_bytes_per_request_off=round(bpr_off, 1),
        pool_bytes_per_request_on=round(bpr_on, 1),
        pool_bytes_reduction_x=round(bpr_off / max(bpr_on, 1e-9), 3),
        bytes_deduplicated_peak=pc["peak_bytes_deduplicated"],
        prefix_hit_rate=pc["hit_rate"],
        prefix_hits=pc["hits"],
        prefix_tokens_reused=pc["prefix_tokens_reused"],
        cow_copies=pc["cow_copies"],
        goodput_on_tokens_per_sec=round(useful / span_on, 3),
        goodput_off_tokens_per_sec=round(useful / span_off, 3),
        preamble_tokens=pre_n,
        requests=n,
        slots=args.slots,
        prefill_chunk_tokens=args.prefill_chunk,
        kv_quant=bool(args.kv_quant),
        preset=args.preset,
        dtype=str(jnp.dtype(cfg.dtype).name),
        platform=jax.devices()[0].platform,
        device_kind=getattr(jax.devices()[0], "device_kind", "?"),
        timing="readback_barrier",
    )
    if on_tpu():
        persist_result("serve_prefix", rec)


if __name__ == "__main__":
    main()
