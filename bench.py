"""DDP MNIST samples/sec/chip + TransformerLM MFU, on the chip or not at all.

Runs the framework's DDP MNIST training step (ConvNet, dropout on, SGD —
the reference's stock hot loop, SURVEY.md §3.3) on all visible TPU devices
and prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": R, "mfu": M, ...}

vs_baseline compares against the measured reference config #1 (stock torch
DDP MNIST, 2-rank gloo CPU — benchmarks/baseline_measured.json; re-measure
with benchmarks/torch_reference_mnist.py): batch 64 per chip, same synthetic
data generator, dropout active.

"mfu" is the single-chip TransformerLM model-FLOP utilization: achieved
FLOP/s of a full bf16 train step (fwd+bwd+adamw) divided by the chip's peak
bf16 FLOP/s from `_PEAK_BF16`.

There is no fallback: with no TPU the process exits nonzero naming what it
found, an unknown `device_kind` is an error, and a failed phase fails the
run. ROADMAP S1 replaces this file with the cell matrix; `chip_smoke.py` is
the bring-up proof.
"""

import json
import os
import sys
import time

# bf16 peak FLOP/s per chip, keyed by substring of jax Device.device_kind.
# Public spec-sheet numbers (cloud.google.com/tpu docs).
_PEAK_BF16 = [
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
]


def _peak_flops(device_kind: str) -> float:
    """Spec-sheet bf16 peak for ``device_kind``; a device the table does
    not know is an error, not a default."""
    dk = device_kind.lower()
    for key, peak in _PEAK_BF16:
        if key in dk:
            return peak
    raise ValueError(
        f"no bf16 peak on record for device_kind {device_kind!r}; add it "
        "to bench._PEAK_BF16 with its source"
    )


def _sync(jax, x) -> float:
    """Wait for ``x`` on the device and return it as a float."""
    return float(jax.block_until_ready(x))


def _steady_rate(rates):
    """Median of the windows after the first: the first timed window of
    a freshly compiled program has run slower than the rest (ROADMAP S2
    — cause not yet found), later windows jitter. Even-length tails
    average the middle two (a true median, not the faster window).
    Every window is recorded on the row."""
    if len(rates) <= 1:
        return rates[0]
    tail = sorted(rates[1:])
    mid = len(tail) // 2
    if len(tail) % 2:
        return tail[mid]
    # no rounding here: callers feed rates at any scale (samples/s or
    # 1/ms) and round for display themselves
    return (tail[mid - 1] + tail[mid]) / 2


def _bench_ddp_mnist(jax, tdx, steps_per_call: int):
    """Reference config #1: DDP MNIST ConvNet samples/sec/chip at
    ``steps_per_call`` optimizer steps per dispatch (1 = the dispatch
    regime of the measured torch reference; K>1 = the framework's fused
    path, K unrolled steps in one compiled program — same math, pinned by
    tests/test_ddp.py)."""
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_example_tpu.models import ConvNet

    batch_per_chip = int(os.environ.get("BENCH_BATCH", "64"))
    warmup = int(os.environ.get("BENCH_WARMUP", "20"))
    steps = int(os.environ.get("BENCH_STEPS", "200"))
    K = int(steps_per_call)
    steps = (steps // K) * K or K
    warmup = max(warmup // K, 1) * K

    world = tdx.get_world_size()
    global_batch = batch_per_chip * world

    model = ConvNet()
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, 28, 28, 1)))
    ddp = tdx.DistributedDataParallel(model, params)
    opt = optax.sgd(0.01, momentum=0.5)

    def loss_fn(logits, y):
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    step = ddp.make_train_step(
        opt, loss_fn, has_rng=True,
        **({"steps_per_call": K, "unroll_steps": True} if K > 1 else {}),
    )
    opt_state = step.init_opt_state(ddp.params)

    # Device-resident inputs, like the torch reference's preloaded host
    # tensors: feeding numpy would re-transfer ~200KB host->device every
    # step, which dominates a step this small. Shard over the dp axis up
    # front (the step's in_spec); with K>1 the leading axis is the step.
    gen = np.random.default_rng(0)
    x = gen.standard_normal((global_batch, 28, 28, 1)).astype(np.float32)
    y = gen.integers(0, 10, global_batch).astype(np.int32)
    spec = P(None, step.axis) if K > 1 else P(step.axis)
    if K > 1:
        x = np.broadcast_to(x, (K,) + x.shape)
        y = np.broadcast_to(y, (K,) + y.shape)
    x = jax.device_put(x, NamedSharding(step.mesh, spec))
    y = jax.device_put(y, NamedSharding(step.mesh, spec))
    # dropout keys pre-split off the hot path: the loop body is one dispatch
    all_keys = jax.random.split(rng, warmup + steps)
    keys = [
        all_keys[i : i + K] if K > 1 else all_keys[i]
        for i in range(0, warmup + steps, K)
    ]
    n_warm = warmup // K

    p = ddp.params
    for key in keys[:n_warm]:
        p, opt_state, loss = step(p, opt_state, x, y, key)
    jax.block_until_ready(loss)

    # BENCH_WINDOWS timed windows; the reported rate is _steady_rate
    # (median of windows[1:]) and every window is on the row, so a ramp
    # in the first one is visible, not hidden.
    n_windows = max(int(os.environ.get("BENCH_WINDOWS", "3")), 1)
    rates = []
    with _maybe_trace(jax):
        for _w in range(n_windows):
            t0 = time.perf_counter()
            for key in keys[n_warm:]:
                p, opt_state, loss = step(p, opt_state, x, y, key)
            final_loss = _sync(jax, loss[-1] if K > 1 else loss)
            dt = time.perf_counter() - t0
            rates.append(round(steps * global_batch / dt / world, 1))

    return _steady_rate(rates), {
        "warmup": warmup,
        "steps": steps,
        "steps_per_dispatch": K,
        "windows": rates,
        "reported": (
            "median_after_ramp" if n_windows > 1 else "single_window_with_ramp"
        ),
        "final_loss": round(final_loss, 4),
        # per-rank train-state footprint (ZeRO weight-update sharding is
        # the trainer default: opt state ~1/world per device)
        "memory": step.memory_report(p, opt_state),
    }


def _bench_mfu(jax):
    """Single-chip TransformerLM bf16 train-step MFU vs the table's peak.

    MFU numerator is the ANALYTIC model-FLOP count (PaLM appendix B
    convention: (6*N + 12*n_layers*d_model*seq) * tokens per step), so the
    number stays comparable across rounds and JAX versions. The compiled
    program's own cost_analysis FLOPs (optimizer + remat included) are
    reported separately as hardware-FLOP utilization (hfu).
    """
    import jax.numpy as jnp
    import numpy as np
    import optax

    from pytorch_distributed_example_tpu.models import TransformerConfig, TransformerLM
    from pytorch_distributed_example_tpu.ops.flash_attention import (
        resolved_block_sizes,
    )

    peak = _peak_flops(jax.devices()[0].device_kind)
    B = int(os.environ.get("BENCH_MFU_BATCH", "8"))
    L = int(os.environ.get("BENCH_MFU_SEQ", "512"))
    warmup = int(os.environ.get("BENCH_MFU_WARMUP", "5"))
    steps = int(os.environ.get("BENCH_MFU_STEPS", "30"))
    D_MODEL, N_LAYERS = 512, 8

    cfg = TransformerConfig(
        vocab_size=32000, d_model=D_MODEL, n_layers=N_LAYERS, n_heads=8,
        max_seq_len=L, dtype=jnp.bfloat16, use_flash=True,
    )
    model = TransformerLM(cfg)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 32000, (B, L)), jnp.int32
    )
    params = model.init(jax.random.PRNGKey(0), toks)
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, toks):
        loss, grads = jax.value_and_grad(
            lambda p: _mfu_loss(model, p, toks)
        )(params)
        updates, opt_state2 = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state2, loss

    bq, bk = resolved_block_sizes(L)
    info = {"flash_used": True, "flash_block_q": bq, "flash_block_k": bk,
            "peak_tflops": peak / 1e12, "peak_source": "spec_sheet"}

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    model_flops_per_step = (
        6.0 * n_params + 12.0 * N_LAYERS * D_MODEL * L
    ) * B * L
    cost = step.lower(params, opt_state, toks).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hw_flops_per_step = float(cost.get("flops", 0.0))

    # BENCH_MFU_SCAN=K>1: K full optimizer steps per dispatch via
    # lax.scan (identical math; host dispatch amortized K-fold).
    scan_k = int(os.environ.get("BENCH_MFU_SCAN", "8"))
    if scan_k > 1:
        steps = max(steps // scan_k, 1) * scan_k
        warmup = max(warmup // scan_k, 1)
        base_step = step

        @jax.jit
        def step(params, opt_state, toks):  # noqa: F811 — same signature
            def body(c, _):
                p, o, _l = base_step(c[0], c[1], toks)
                return (p, o), _l

            (p, o), losses = jax.lax.scan(
                body, (params, opt_state), None, length=scan_k
            )
            return p, o, losses[-1]

        info["mfu_steps_per_dispatch"] = scan_k
    dispatches = steps // scan_k if scan_k > 1 else steps

    for _ in range(max(warmup, 1)):
        params, opt_state, loss = step(params, opt_state, toks)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(dispatches):
        params, opt_state, loss = step(params, opt_state, toks)
    final_loss = _sync(jax, loss)
    dt = time.perf_counter() - t0

    achieved = model_flops_per_step * steps / dt
    info["mfu_final_loss"] = round(final_loss, 4)
    if hw_flops_per_step:
        # cost_analysis cannot see inside the flash custom-call, so hfu
        # UNDERSTATES hardware utilization (disclosed, not corrected)
        info["hfu_note"] = "XLA-counted flops exclude the flash custom-call"
    if os.environ.get("BENCH_BREAKDOWN"):
        info["breakdown_ms"] = _mfu_breakdown(
            jax, model, params, toks, steps, dt / steps
        )
    hfu = hw_flops_per_step * steps / dt / peak
    return achieved / peak, achieved / 1e12, hfu, info


def _mfu_loss(model, params, toks):
    """THE loss of the MFU step — single definition shared by the timed
    train step and the breakdown programs so they can't diverge."""
    import optax

    logits = model.apply(params, toks)
    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], toks[:, 1:]
    ).mean()


def _mfu_breakdown(jax, model, params, toks, steps, step_s):
    """{fwd, fwd_bwd, full_step} avg ms — the step's composition."""

    @jax.jit
    def fwd(p, t):
        return model.apply(p, t)

    @jax.jit
    def fwd_bwd(p, t):
        return jax.value_and_grad(lambda pp: _mfu_loss(model, pp, t))(p)

    out = {"full_step": round(step_s * 1e3, 3)}
    for name, fn in (("fwd", fwd), ("fwd_bwd", fwd_bwd)):
        jax.block_until_ready(fn(params, toks))  # compile
        t0 = time.perf_counter()
        for _ in range(steps):
            r = fn(params, toks)
        jax.block_until_ready(r)
        out[name] = round((time.perf_counter() - t0) / steps * 1e3, 3)
    return out


class _maybe_trace:
    """Optional jax.profiler.trace wrapper: BENCH_TRACE=<dir> saves the
    timed loop's device timeline. Point it under `chiprun_out/` (MB-scale,
    git-ignored) to bring a capture back from the chip."""

    def __init__(self, jax):
        self.jax = jax
        self.dir = os.environ.get("BENCH_TRACE") or None
        self._cm = None

    def __enter__(self):
        if self.dir:
            self._cm = self.jax.profiler.trace(self.dir)
            self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        if self._cm is not None:
            self._cm.__exit__(*exc)
        return False


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"bench.py: no TPU — jax.devices()[0].platform is "
            f"{dev.platform!r} ({dev.device_kind}); this benchmark runs on "
            "the chip or not at all"
        )
    _peak_flops(dev.device_kind)  # an unknown device fails before any work

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import pytorch_distributed_example_tpu as tdx
    from benchmarks.common import pin_numerics
    from pytorch_distributed_example_tpu._compat import enable_compile_cache

    enable_compile_cache()
    pin_numerics()  # hardware-rate matmuls, stated outright
    tdx.init_process_group(backend="xla")

    # The headline and its vs_baseline ratio come from the PER-STEP-
    # dispatch row — the dispatch regime of the measured torch reference.
    # The fused number (BENCH_SCAN_STEPS per dispatch) is measured
    # separately and reported as a labelled capability metric.
    per_chip, run_meta = _bench_ddp_mnist(jax, tdx, steps_per_call=1)
    run_meta["dispatch_mode"] = "per_step"
    scan_k = int(os.environ.get("BENCH_SCAN_STEPS", "8"))
    fused = _bench_ddp_mnist(jax, tdx, scan_k) if scan_k > 1 else None
    mfu, achieved_tflops, hfu, mfu_info = _bench_mfu(jax)

    baseline_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "benchmarks", "baseline_measured.json",
    )
    with open(baseline_path) as f:
        ref = json.load(f)["samples_per_sec_per_chip"]

    out = {
        "metric": "ddp_mnist_samples_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "samples/s/chip",
        "world": tdx.get_world_size(),
        **run_meta,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "vs_baseline": round(per_chip / ref, 3),
        "mfu": round(mfu, 4),
        "mfu_tflops": round(achieved_tflops, 2),
        "hfu": round(hfu, 4),
        **mfu_info,
    }
    if fused is not None:
        fused_rate, fused_meta = fused
        out["fused_steps_samples_per_sec_per_chip"] = round(fused_rate, 1)
        out["fused_steps_meta"] = {
            k: fused_meta[k]
            for k in ("steps_per_dispatch", "windows", "reported")
        }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
