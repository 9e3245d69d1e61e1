"""TransformerLM training throughput — BASELINE.json configs #4/#5.

Causal-LM train step over a (dp, fsdp, tp) mesh with the canonical 2-D
GSPMD layout (models.transformer.sharding_rules). Default geometry is a
BERT-base-scale model (12L/768d/12H); `--preset llama8b-ish` scales the
config toward the stretch target (fits only on real pods — use with
--dry). Reports tokens/s/chip and model FLOP/s utilization-style totals.

Usage:
  python benchmarks/transformer_lm.py [--preset base|small] [--seq 512]
      [--batch 8] [--bf16] [--tp 1] [--fsdp N] [--flash/--no-flash]
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
    "llama8b-ish": dict(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        d_ff=14336,
    ),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default="base")
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8, help="global batch")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--bf16", action="store_true")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--fsdp", type=int, default=0, help="0 = all remaining devices")
    ap.add_argument("--no-flash", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument(
        "--planner", choices=["off", "traced"], default="off",
        help="'traced': run the TP-decode collective microbench (the "
        "vocab-logits gather + activation gather-matmul, stock vs the "
        "plan/traced.py ring lowering, overlap on/off) instead of the "
        "train loop",
    )
    args = ap.parse_args()
    args.warmup = max(1, args.warmup)  # >=1: compile must precede timing

    if args.planner == "traced":
        return run_tp_decode_planned(args)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_example_tpu.mesh import init_device_mesh
    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
        transformer_sharding_rules,
    )
    from pytorch_distributed_example_tpu.parallel import fully_shard
    from benchmarks.common import device_sync, emit

    n_dev = len(jax.devices())
    tp = args.tp
    fsdp = args.fsdp or (n_dev // tp)
    dp = n_dev // (tp * fsdp)
    mesh = init_device_mesh(("dp", "fsdp", "tp"), (dp, fsdp, tp))

    kw = dict(PRESETS[args.preset])
    cfg = TransformerConfig(
        max_seq_len=args.seq,
        dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        use_flash=not args.no_flash,
        remat=args.remat,
        **kw,
    )
    model = TransformerLM(cfg)
    gen = np.random.default_rng(0)
    toks = jnp.asarray(
        gen.integers(0, cfg.vocab_size, (args.batch, args.seq)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), toks[:1, :])

    mod = fully_shard(
        model, params, mesh, axis="fsdp",
        rules=transformer_sharding_rules("tp", "fsdp"),
        data_axes=("dp", "fsdp"),
    )
    opt = optax.adamw(1e-4)

    def loss_fn(logits, y):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], y[:, 1:]
        ).mean()

    step = mod.make_train_step(opt, loss_fn)
    opt_state = opt.init(mod.params)

    p, s = mod.params, opt_state
    for _ in range(args.warmup):
        p, s, loss = step(p, s, toks, toks)
    device_sync(loss)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        p, s, loss = step(p, s, toks, toks)
    device_sync(loss)
    dt = time.perf_counter() - t0

    tokens = args.steps * args.batch * args.seq
    per_chip = tokens / dt / n_dev
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    # 6ND + attention-flops estimate for train step
    flops = 6.0 * n_params * tokens + 12.0 * kw["n_layers"] * kw["d_model"] * args.seq * tokens
    emit(
        f"transformer_{args.preset}_tokens_per_sec_per_chip",
        per_chip,
        "tokens/s/chip",
        world=n_dev,
        mesh=f"dp{dp}xfsdp{fsdp}xtp{tp}",
        params_m=round(n_params / 1e6, 1),
        model_tflops_per_sec=round(flops / dt / 1e12, 2),
        loss=round(float(loss), 4),
        platform=jax.devices()[0].platform,
        device_kind=getattr(jax.devices()[0], "device_kind", "?"),
        timing="readback_barrier",
    )


def run_tp_decode_planned(args):
    """**transformer_tp_decode_planned** (`--planner traced`): the two
    TP decode collectives ISSUE 20 routes through the trace-time
    planner — the vocab-parallel logits all-gather and the
    sequence-sharded activation gather-matmul — timed stock vs the
    agreed ring lowering (and ring with `TDX_PLANNER_OVERLAP=0`, to
    isolate the per-chunk overlap).  The planned logits must be BITWISE
    the stock gather (pure data movement); the gather-matmul is
    CHUNK-exact (bitwise the per-chunk dots) and allclose — not
    necessarily bitwise — vs the one-shot dot, whose shape-dependent
    tiling reassociates the within-row sum at hardware matmul
    precision."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import device_sync, emit
    from pytorch_distributed_example_tpu._compat import shard_map_fn
    from pytorch_distributed_example_tpu.parallel import (
        tensor_parallel as tp_mod,
    )
    from pytorch_distributed_example_tpu.plan import traced
    from jax.sharding import Mesh, PartitionSpec as P

    n_dev = len(jax.devices())
    W = args.tp if args.tp > 1 else n_dev
    mesh = Mesh(np.array(jax.devices()[:W]), ("tp",))
    kw = PRESETS[args.preset]
    d, V = kw["d_model"], kw["vocab_size"]
    B = args.batch
    gen = np.random.default_rng(0)
    h = jnp.asarray(gen.standard_normal((B, d)), jnp.float32)
    emb = jnp.asarray(gen.standard_normal((W, d, V // W)), jnp.float32)
    xs = jnp.asarray(gen.standard_normal((W * B, d)), jnp.float32)
    wm = jnp.asarray(gen.standard_normal((d, d)), jnp.float32)

    def build():
        logits = jax.jit(shard_map_fn(
            lambda hh, ee: tp_mod.vocab_parallel_logits(
                hh, ee[0], "tp"
            )[None],
            mesh=mesh, in_specs=(P(), P("tp")), out_specs=P("tp"),
        ))
        agmm = jax.jit(shard_map_fn(
            lambda xx, ww: tp_mod.gathered_matmul(xx, ww, "tp")[None],
            mesh=mesh, in_specs=(P("tp"), P()), out_specs=P("tp"),
        ))
        return logits, agmm

    def timed(fn, fnargs):
        out = fn(*fnargs)
        device_sync(out)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            out = fn(*fnargs)
        device_sync(out)
        return (time.perf_counter() - t0) / max(args.steps, 1), out

    env_keys = ("TDX_COLLECTIVE_PLANNER", "TDX_PLANNER_OVERLAP",
                "TDX_PLANNER_FORCE")
    saved = {k: os.environ.get(k) for k in env_keys}
    rows = {}
    try:
        for variant, overlap in (("stock", None), ("planned", "1"),
                                 ("overlap_off", "0")):
            for k in env_keys:
                os.environ.pop(k, None)
            traced.reset()
            if variant != "stock":
                os.environ["TDX_COLLECTIVE_PLANNER"] = "1"
                os.environ["TDX_PLANNER_OVERLAP"] = overlap
                # the agreed-table entries prepare() would install: a
                # ring gather for each decode bucket (probe-selected on
                # real multichip topologies; pinned here so the CPU row
                # is deterministic)
                traced.seed("all_gather", "ring", world=W,
                            nbytes=B * (V // W) * 4, source="bench")
                traced.seed("all_gather", "ring", world=W,
                            nbytes=B * d * 4, source="bench")
            logits_fn, agmm_fn = build()
            t_lg, out_lg = timed(logits_fn, (h, emb))
            t_mm, out_mm = timed(agmm_fn, (xs, wm))
            rows[variant] = dict(
                logits_s=t_lg, agmm_s=t_mm,
                lg=np.asarray(out_lg), mm=np.asarray(out_mm),
            )
    finally:
        traced.reset()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v

    st, pl = rows["stock"], rows["planned"]
    # the overlapped matmul's contract: bitwise the per-chunk dots
    mm_ref = np.concatenate(
        [np.asarray(jnp.dot(xs[i * B:(i + 1) * B], wm)) for i in range(W)]
    )
    mm_rel = float(np.max(
        np.abs(pl["mm"][0] - st["mm"][0])
        / (np.abs(st["mm"][0]) + 1e-30)
    ))
    emit(
        "transformer_tp_decode_planned",
        st["logits_s"] / pl["logits_s"] if pl["logits_s"] else 0.0,
        "x_logits_gather_time",
        world=W,
        preset=args.preset,
        steps=args.steps,
        schedule="ring",
        stock_logits_s=round(st["logits_s"], 6),
        planned_logits_s=round(pl["logits_s"], 6),
        overlap_off_logits_s=round(rows["overlap_off"]["logits_s"], 6),
        stock_agmm_s=round(st["agmm_s"], 6),
        planned_agmm_s=round(pl["agmm_s"], 6),
        overlap_off_agmm_s=round(rows["overlap_off"]["agmm_s"], 6),
        agmm_speedup_x=round(
            st["agmm_s"] / pl["agmm_s"] if pl["agmm_s"] else 0.0, 4
        ),
        logits_bitwise=st["lg"].tobytes() == pl["lg"].tobytes(),
        agmm_chunk_exact=pl["mm"][0].tobytes() == mm_ref.tobytes(),
        agmm_max_rel_vs_stock=mm_rel,
        platform=jax.devices()[0].platform,
    )


if __name__ == "__main__":
    main()
