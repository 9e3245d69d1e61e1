"""BERT-base fine-tune DDP throughput — BASELINE.json config #4.

Sequence classification over synthetic token data: BERT-base geometry
(12L/768d/12H/3072ff, bidirectional attention, post-LN), DDP over every
visible device, AdamW. Reports samples/s/chip and tokens/s/chip.

Usage: python benchmarks/bert_finetune.py [--preset base|small]
    [--batch 16] [--seq 128] [--steps 30] [--bf16]
"""

from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

PRESETS = {
    "small": dict(vocab_size=30522, d_model=256, n_layers=4, n_heads=8, d_ff=1024),
    "base": dict(vocab_size=30522, d_model=768, n_layers=12, n_heads=12, d_ff=3072),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="base")
    ap.add_argument("--batch", type=int, default=16, help="per-chip batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--bf16", action="store_true")
    args = ap.parse_args()
    args.warmup = max(1, args.warmup)  # >=1: compile must precede timing

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import pytorch_distributed_example_tpu as tdx
    from benchmarks.common import device_sync, emit
    from pytorch_distributed_example_tpu.models import (
        BertConfig,
        BertForSequenceClassification,
    )

    if not tdx.is_initialized():
        tdx.init_process_group(backend="xla")
    W = tdx.get_world_size()
    gb = args.batch * W

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    cfg = BertConfig(
        max_seq_len=args.seq, dtype=dtype, dropout=0.1, **PRESETS[args.preset]
    )
    model = BertForSequenceClassification(cfg, num_labels=2)

    gen = np.random.default_rng(0)
    ids0 = jnp.asarray(gen.integers(0, cfg.vocab_size, (1, args.seq)))
    params = model.init(jax.random.PRNGKey(0), ids0)
    ddp = tdx.DistributedDataParallel(model, params)
    opt = optax.adamw(2e-5)  # the classic fine-tune recipe

    def loss_fn(logits, y):
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    # dropout active during fine-tune (train=True through DDP's rng path)
    step = ddp.make_train_step(opt, loss_fn, has_rng=True)
    opt_state = opt.init(ddp.params)

    x = jnp.asarray(gen.integers(0, cfg.vocab_size, (gb, args.seq)))
    y = jnp.asarray(gen.integers(0, 2, gb), jnp.int32)

    p = ddp.params
    for i in range(args.warmup):
        p, opt_state, loss = step(p, opt_state, x, y, jax.random.PRNGKey(i))
    device_sync(loss)

    t0 = time.perf_counter()
    for i in range(args.steps):
        p, opt_state, loss = step(
            p, opt_state, x, y, jax.random.PRNGKey(args.warmup + i)
        )
    device_sync(loss)
    dt = time.perf_counter() - t0

    per_chip = args.steps * gb / dt / W
    emit(
        "bert_finetune_ddp_samples_per_sec_per_chip",
        per_chip,
        "samples/s/chip",
        world=W,
        preset=args.preset,
        seq=args.seq,
        batch_per_chip=args.batch,
        tokens_per_sec_per_chip=round(per_chip * args.seq, 1),
        dtype=str(jnp.dtype(dtype).name),
        loss=round(float(loss), 4),
        platform=jax.devices()[0].platform,
        device_kind=getattr(jax.devices()[0], "device_kind", "?"),
        timing="readback_barrier",
    )


if __name__ == "__main__":
    main()
