"""Flagship-scale perf point — BASELINE.json configs[4], round-2 VERDICT #3.

Two honestly-scoped modes (8B does not fit one v5e chip):

* ``--mode mfu``: the largest-that-fits (~1B param, bf16) TransformerLM
  single-chip MFU bench — full train step (fwd+bwd+adamw), per-block
  remat, flash attention. TPU only (exits nonzero elsewhere).
* ``--mode memory8b``: the TRUE Llama-3-8B FSDP-full-shard (ZeRO-3)
  GSPMD layout, AOT-lowered and compiled over an 8-device mesh — no
  execution — reporting XLA's per-device memory analysis, proving the
  8B layout fits a v4-8-class slice. Runs on the virtual CPU mesh.

Llama-3-8B geometry (public model card): d=4096, 32 layers, 32 heads,
8 KV heads (GQA), ffn 14336, vocab 128256, seq 4096 (the 8192-native
model benched at 4k ctx, matching torch FSDP recipes).

Usage:
    python benchmarks/llama_scaled.py --mode memory8b      # any host
    python benchmarks/llama_scaled.py --mode mfu           # TPU
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

# ~1B bf16 config that fits one 16 GB chip with bf16 optimizer state +
# per-block remat: params ~0.94 GB*2B, grads 2B, adamw m+v 4B -> ~7.5 GB.
CFG_1B = dict(
    vocab_size=32000,
    d_model=2048,
    n_layers=16,
    n_heads=16,
    d_ff=5504,
)
CFG_8B = dict(
    vocab_size=128256,
    d_model=4096,
    n_layers=32,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
)


def _build(cfg_kw, seq, bf16_params, use_flash, remat=True):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
    )

    cfg = TransformerConfig(
        max_seq_len=seq,
        dtype=jnp.bfloat16,
        use_flash=use_flash,
        remat=remat,
        **cfg_kw,
    )
    model = TransformerLM(cfg)
    return model, cfg


def _n_params(tree):
    import jax

    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def _analytic_flops(n_params, n_layers, d_model, seq, tokens):
    # PaLM appendix-B convention, as in bench.py: 6N (fwd+bwd matmuls)
    # + 12*l*d*L attention term, per token.
    return (6.0 * n_params + 12.0 * n_layers * d_model * seq) * tokens


def run_mfu(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from benchmarks.common import emit

    from benchmarks.common import on_tpu

    dev = jax.devices()[0]
    kind = dev.device_kind
    if not on_tpu():
        sys.exit(
            f"llama_scaled --mode mfu: no TPU (platform {dev.platform!r}); "
            "the single-chip HBM-resident 1B model is measured on the chip "
            "or not at all"
        )

    from bench import _peak_flops  # the one peaks table; unknown kind raises

    peak = _peak_flops(kind)
    B, L = args.batch, args.seq
    # remat trades MFU for memory; ~1B bf16 states (~7.6 GB) may leave
    # room to skip it on a 16 GB chip — try --no-remat on hardware
    model, cfg = _build(
        CFG_1B, L, True, use_flash=not args.no_flash, remat=not args.no_remat
    )
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (B, L)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), toks)
    # bf16 master weights + bf16 adamw state: the fit-on-one-chip layout
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    n_params = _n_params(params)
    opt = optax.adamw(1e-4)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, toks):
        def lf(p):
            logits = model.apply(p, toks)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1].astype(jnp.float32), toks[:, 1:]
            ).mean()

        loss, grads = jax.value_and_grad(lf)(params)
        updates, opt_state2 = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state2, loss

    from benchmarks.common import device_sync

    params, opt_state, loss = step(params, opt_state, toks)  # compile
    device_sync(loss)
    for _ in range(args.warmup):
        params, opt_state, loss = step(params, opt_state, toks)
    device_sync(loss)
    # BENCH_TRACE=<dir>: same knob and wrapper as bench.py — the timed
    # steps land on a jax.profiler timeline (flash custom-calls visible)
    from bench import _maybe_trace, _steady_rate

    # BENCH_WINDOWS repeated timed windows (default 3): the reported step
    # time is the median of the windows after the first, with every
    # window's ms recorded on the row (same methodology and rationale as
    # bench.py's headline).
    n_windows = max(int(os.environ.get("BENCH_WINDOWS", "3")), 1)
    window_ms = []
    with _maybe_trace(jax):
        for _w in range(n_windows):
            t0 = time.perf_counter()
            for _ in range(args.steps):
                params, opt_state, loss = step(params, opt_state, toks)
            final_loss = device_sync(loss)
            window_ms.append(
                round((time.perf_counter() - t0) / args.steps * 1e3, 1)
            )
    # _steady_rate picks the median of the post-ramp windows; it operates
    # on rates, so feed 1/ms and invert back
    dt = 1.0 / _steady_rate([1.0 / m for m in window_ms]) / 1e3

    flops = _analytic_flops(n_params, cfg.n_layers, cfg.d_model, L, B * L)
    mfu = flops / dt / peak
    rec = emit(
        "llama_scaled_mfu",
        round(mfu, 4),
        "mfu",
        n_params=n_params,
        tflops=round(flops / dt / 1e12, 2),
        tokens_per_sec=round(B * L / dt, 1),
        step_ms=round(dt * 1e3, 1),
        window_step_ms=window_ms,
        reported="median_after_ramp" if n_windows > 1 else "single_window",
        batch=B,
        seq=L,
        remat=not args.no_remat,
        platform=dev.platform,
        device_kind=kind,
        peak_tflops=peak / 1e12,
        peak_source="spec_sheet",
        final_loss=round(final_loss, 4),
    )
    from benchmarks.common import persist_result

    # TDX_MFU_KEY_SUFFIX keeps e.g. pre-bake and tuned-blocks runs as
    # separate rows.
    suffix = os.environ.get("TDX_MFU_KEY_SUFFIX", "")
    persist_result("llama_scaled_mfu" + suffix, rec)


def run_memory8b(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.common import emit
    from pytorch_distributed_example_tpu.models.transformer import sharding_rules
    from pytorch_distributed_example_tpu.parallel import sharding as shd
    from pytorch_distributed_example_tpu.parallel.fsdp import make_fsdp_train_step

    import optax

    # --target tpu: AOT-compile against a DEVICELESS TPU topology
    # (jax.experimental.topologies) so XLA's *TPU* backend does the
    # scheduling — its temp_size honors the per-block remat and the
    # flash kernel, unlike the CPU backend's (round-3 VERDICT #6). Works
    # with no TPU attached: the PJRT TPU compiler runs on the host.
    target = args.target
    topo_devices = None
    if target in ("tpu", "auto"):
        try:
            from jax.experimental import topologies

            topo = topologies.get_topology_desc(
                platform="tpu", topology_name=args.topology
            )
            topo_devices = list(topo.devices)
            target = "tpu"
        except Exception as e:
            if target == "tpu":
                raise
            print(f"# tpu topology unavailable ({type(e).__name__}: "
                  f"{str(e)[:200]}); falling back to attached devices",
                  file=sys.stderr)
            target = "cpu"

    pool = topo_devices if topo_devices is not None else jax.devices()
    n_dev = len(pool)
    fsdp = args.fsdp or n_dev // args.tp
    devs = np.array(pool[: fsdp * args.tp]).reshape(fsdp, args.tp)
    mesh = Mesh(devs, ("fsdp", "tp"))

    # Flash attention is the real TPU path; the CPU target can't compile
    # the Mosaic kernel, so it falls back to dense (the old caveat).
    model, cfg = _build(CFG_8B, args.seq, True, use_flash=(target == "tpu"))
    toks_abs = jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)
    abs_params = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1, args.seq), jnp.int32)),
        jax.random.PRNGKey(0),
    )
    abs_params = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16), abs_params
    )
    n_params = _n_params(abs_params)
    rules = sharding_rules(tp_axis="tp", fsdp_axis="fsdp")
    specs = shd.make_param_specs(abs_params, rules, mesh)
    opt = optax.adamw(1e-4)
    abs_opt = jax.eval_shape(opt.init, abs_params)

    step = make_fsdp_train_step(
        model.apply,
        lambda lg, y: optax.softmax_cross_entropy_with_integer_labels(
            lg[:, :-1].astype(jnp.float32), y[:, 1:]
        ).mean(),
        opt,
        mesh,
        specs,
        data_axes=("fsdp",),
        remat=False,  # cfg.remat already checkpoints per block
        donate=True,
    )
    # place abstract leaves on their shardings so AOT lowering sees the
    # true FSDP layout
    abs_params = jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(
            l.shape, l.dtype, sharding=NamedSharding(mesh, s)
        ),
        abs_params,
        specs,
    )
    t0 = time.perf_counter()
    lowered = step.lower(abs_params, abs_opt, toks_abs, toks_abs)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    # XLA's own accounting, no execution (VERDICT #3's requested evidence)
    mem = {}
    try:
        ma = compiled.memory_analysis()
        if isinstance(ma, (list, tuple)):
            ma = ma[0]
        for f in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ):
            v = getattr(ma, f, None)
            if v is not None:
                mem[f] = int(v)
    except Exception as e:
        mem["memory_analysis_error"] = repr(e)

    # Analytic per-device table from the specs (cross-check / fallback):
    # bf16 params+grads+adamw m+v (optax states inherit param dtype),
    # all sharded per the layout.
    axis_sizes = dict(mesh.shape)

    def shard_bytes(leaf, spec):
        denom = 1
        for ax in spec:
            if ax is None:
                continue
            for a in ax if isinstance(ax, tuple) else (ax,):
                denom *= axis_sizes[a]
        return leaf.size * leaf.dtype.itemsize // denom

    p_bytes = sum(
        shard_bytes(l, s)
        for l, s in zip(
            jax.tree_util.tree_leaves(abs_params), jax.tree_util.tree_leaves(specs)
        )
    )
    analytic = {
        "params_bytes_per_device": p_bytes,
        "grads_bytes_per_device": p_bytes,
        "adamw_state_bytes_per_device": 2 * p_bytes,  # m+v in param dtype
        "total_state_bytes_per_device": 4 * p_bytes,
    }
    # STATE memory is the XLA-verified figure: the executable's per-device
    # argument bytes are params + opt state as actually sharded (donated
    # args alias outputs, so they count once); grads live in the same
    # layout, one extra params-worth of temp.
    state_per_dev = mem.get("argument_size_in_bytes", 3 * p_bytes) + p_bytes
    # Activation peak for the TPU path (flash + per-block remat; the
    # CPU backend's temp accounting does NOT honor the remat schedule —
    # probed: temp identical with remat on/off even though the jaxpr
    # carries one remat eqn per block — and uses dense attention, so its
    # temp number is reported raw but does not transfer to TPU):
    # block-input stash (n_layers x B_loc x L x d x 2B) + one block's
    # recompute workspace + the fp32 logit/dlogit slices.
    b_loc = max(args.batch // fsdp, 1)
    act = (
        cfg.n_layers * b_loc * args.seq * cfg.d_model * 2  # stashed block inputs
        + 4 * b_loc * args.seq * cfg.d_model * 2 * 6  # one block live (qkv/ffn)
        + 2 * b_loc * args.seq * cfg.vocab_size * 4 // max(args.tp, 1)
    )
    extra = {}
    if target == "tpu" and "temp_size_in_bytes" in mem:
        # The TPU backend's schedule IS the real accounting: temp covers
        # grads + activations + collective buffers with remat and flash
        # honored. Per-device peak = live arguments + temps (donated
        # outputs alias into arguments).
        total = mem["argument_size_in_bytes"] + mem["temp_size_in_bytes"]
        extra["accounting"] = "xla_tpu_backend"
        extra["activation_bytes_per_device_analytic_crosscheck"] = int(act)
    else:
        total = state_per_dev + act
        extra["accounting"] = "state_xla + activations_analytic"
        extra["activation_bytes_per_device_analytic"] = int(act)
        if target == "tpu":
            # TPU compile ran but memory_analysis failed: the row falls
            # back to the analytic estimate and says so (and is NOT
            # persisted as backend-verified evidence below)
            extra["tpu_memory_analysis_failed"] = mem.get(
                "memory_analysis_error", "temp_size_in_bytes missing"
            )
        else:
            extra["cpu_temp_caveat"] = (
                "temp_size is the CPU backend's schedule (dense attention, "
                "remat not honored by its buffer liveness); TPU uses "
                "flash+remat — run with --target tpu for the real accounting"
            )
    rec = emit(
        "llama_scaled_memory8b",
        round(total / 1e9, 3),
        "GB/device",
        n_params=n_params,
        mesh={"fsdp": fsdp, "tp": args.tp},
        seq=args.seq,
        batch=args.batch,
        target=target,
        topology=(args.topology if target == "tpu" else None),
        flash=(target == "tpu"),
        compile_s=round(compile_s, 1),
        state_bytes_per_device_xla_verified=int(state_per_dev),
        xla_memory_analysis=mem,
        analytic=analytic,
        fits_16gb_hbm=bool(total < 16e9),  # v5e/v5 lite class
        fits_32gb_hbm=bool(total < 32e9),  # v4-8 class (32 GB/chip)
        **extra,
    )
    if target == "tpu" and extra.get("accounting") == "xla_tpu_backend":
        # TPU-backend accounting is durable evidence (VERDICT #6) —
        # persist it like the hardware-measured rows. An analytic
        # fallback (memory_analysis failed) must NOT be stored under
        # the backend-verified key.
        from benchmarks.common import persist_result

        persist_result("llama_scaled_memory8b_tpu", rec)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["mfu", "memory8b"], default="memory8b")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--no-flash", action="store_true")
    ap.add_argument("--no-remat", action="store_true",
                    help="mfu mode: skip per-block remat (more HBM, "
                         "higher MFU if it fits)")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--fsdp", type=int, default=None)
    ap.add_argument("--target", choices=["auto", "cpu", "tpu"], default="auto",
                    help="memory8b: 'tpu' AOT-compiles against a deviceless "
                         "TPU topology (real TPU memory accounting, no "
                         "hardware needed); 'cpu' uses attached devices")
    ap.add_argument("--topology", default="v5e:2x4",
                    help="deviceless TPU topology (v5e:2x4 = 8 chips x "
                         "16 GB; also e.g. v4:2x2x2)")
    args = ap.parse_args()
    if args.mode == "mfu":
        args.batch = args.batch or 8
        args.seq = args.seq or 1024
        run_mfu(args)
    else:
        args.batch = args.batch or 8
        args.seq = args.seq or 4096
        run_memory8b(args)


if __name__ == "__main__":
    main()
