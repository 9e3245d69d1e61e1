"""Autoregressive decode throughput — KV-cache generation bench.

Measures steady-state decode tokens/s (prefill excluded) for the
TransformerLM KV-cache path at a given geometry. The figure of merit on
TPU is decode tokens/s/chip; at batch 1 decode is HBM-bandwidth-bound
(every step streams the weights), so tokens/s ~ HBM GB/s / param bytes.

Usage: python benchmarks/generate_bench.py [--preset base|small]
    [--batch 8] [--prompt 128] [--new 128] [--bf16]
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

PRESETS = {
    "small": dict(vocab_size=32000, d_model=256, n_layers=4, n_heads=8),
    "base": dict(vocab_size=32000, d_model=768, n_layers=12, n_heads=12),
    "large": dict(vocab_size=32000, d_model=1024, n_layers=24, n_heads=16),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="base")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=128)
    ap.add_argument("--new", type=int, default=128)
    ap.add_argument("--bf16", action="store_true")
    args = ap.parse_args()
    if args.new < 2:
        ap.error("--new must be >= 2 (decode-only timing subtracts a "
                 "prefill-only call; --new 1 has no decode loop to measure)")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import device_sync, emit
    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
        generate,
    )

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    cfg = TransformerConfig(
        # exactly the measured window: decode attends the FULL static
        # cache each step, so extra tail would inflate per-step cost
        max_seq_len=args.prompt + args.new,
        dtype=dtype,
        use_flash=False,  # decode path is cache attention, not flash
        **PRESETS[args.preset],
    )
    model = TransformerLM(cfg)
    gen = np.random.default_rng(0)
    prompt = jnp.asarray(
        gen.integers(0, cfg.vocab_size, (args.batch, args.prompt)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), prompt)

    # warmup: compiles prefill + decode body (both call shapes)
    out = generate(model, params, prompt, args.new, rng=jax.random.PRNGKey(1))
    device_sync(out)
    out = generate(model, params, prompt, 1, rng=jax.random.PRNGKey(1))
    device_sync(out)

    # steady-state decode = full call minus a prefill-only call, so the
    # reported tokens/s is decode-only as the metric name promises
    t0 = time.perf_counter()
    out = generate(model, params, prompt, 1, rng=jax.random.PRNGKey(2))
    device_sync(out)
    dt_prefill = time.perf_counter() - t0

    t0 = time.perf_counter()
    out = generate(model, params, prompt, args.new, rng=jax.random.PRNGKey(2))
    device_sync(out)
    dt_full = time.perf_counter() - t0
    dt = max(dt_full - dt_prefill, 1e-9)

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    toks = args.batch * (args.new - 1)  # tokens produced by the decode loop
    emit(
        "decode_tokens_per_sec",
        toks / dt,
        "tokens/s",
        preset=args.preset,
        batch=args.batch,
        prompt=args.prompt,
        new_tokens=args.new,
        params_m=round(n_params / 1e6, 1),
        dtype=str(jnp.dtype(dtype).name),
        per_seq_tokens_per_sec=round((args.new - 1) / dt, 1),
        prefill_ms=round(dt_prefill * 1e3, 1),
        platform=jax.devices()[0].platform,
        device_kind=getattr(jax.devices()[0], "device_kind", "?"),
        timing="readback_barrier",
    )


if __name__ == "__main__":
    main()
