"""Deviceless TPU-target AOT checks: compile evidence + roofline MFU
ceilings with no chip attached — how a builder checks a kernel or a
step before paying for the chip.

The PJRT TPU compiler runs fine on the host against a compile-only
topology (jax.experimental.topologies), so three things become
checkable with zero TPU hardware:

1. The flash-attention Pallas kernel COMPILES for the TPU target at
   every candidate block size (so a short real-hardware window never
   burns time on candidates Mosaic rejects).
2. The MFU bench steps (headline 512d/8L and the ~1B llama config)
   compile for one v5e chip, with XLA's own cost model (FLOPs, bytes
   accessed) and memory analysis recorded.
3. A ROOFLINE CEILING for each step: the step cannot run faster than
   max(hw_flops/peak_flops, bytes/hbm_bw) seconds, so
   mfu_ceiling = model_flops / (time_lb * peak_flops). Also the remat
   recompute tax: hw_flops(remat)/hw_flops(no remat).

All rows are persisted with evidence="aot_compile_only" — these are
compiler facts, not measurements.

Usage: python benchmarks/tpu_aot_check.py   (CPU-pins itself)
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

# Public spec-sheet numbers (cloud.google.com/tpu docs): bf16 peak
# FLOP/s and HBM bandwidth per chip, keyed by device_kind substring.
_CHIP_SPECS = {
    "v5 lite": (197e12, 819e9),
    "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
    "v3": (123e12, 900e9),
}


def _specs(kind: str):
    kind = kind.lower()
    for key, spec in _CHIP_SPECS.items():
        if key in kind:
            return spec
    return (197e12, 819e9)  # default to the v5e class this repo targets


def _single_device():
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(
        platform="tpu",
        topology_name=os.environ.get("TDX_AOT_TOPO", "v5e:2x2"),
    )
    return topo.devices[0]


def _cost(compiled):
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def _mem(compiled):
    ma = compiled.memory_analysis()
    if isinstance(ma, (list, tuple)):
        ma = ma[0]
    return {
        "argument_size_in_bytes": int(ma.argument_size_in_bytes),
        "output_size_in_bytes": int(ma.output_size_in_bytes),
        "temp_size_in_bytes": int(ma.temp_size_in_bytes),
        "alias_size_in_bytes": int(ma.alias_size_in_bytes),
    }


def _compile_train_step(dev, cfg_kw, L, B, use_flash, remat):
    """AOT-compile a full bf16 train step (fwd+bwd+adamw) for one chip."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import SingleDeviceSharding

    from benchmarks.llama_scaled import _build

    model, cfg = _build(cfg_kw, L, True, use_flash=use_flash, remat=remat)
    sharding = SingleDeviceSharding(dev)

    toks_abs = jax.ShapeDtypeStruct((B, L), jnp.int32, sharding=sharding)
    abs_params = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1, L), jnp.int32)),
        jax.random.PRNGKey(0),
    )
    abs_params = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16, sharding=sharding),
        abs_params,
    )
    opt = optax.adamw(1e-3)
    abs_opt = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        jax.eval_shape(opt.init, abs_params),
    )

    def step(params, opt_state, toks):
        def lf(p):
            logits = model.apply(p, toks)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1].astype(jnp.float32), toks[:, 1:]
            ).mean()

        loss, grads = jax.value_and_grad(lf)(params)
        updates, opt_state2 = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state2, loss

    t0 = time.perf_counter()
    compiled = (
        jax.jit(step, donate_argnums=(0, 1))
        .lower(abs_params, abs_opt, toks_abs)
        .compile()
    )
    n_params = sum(
        int(l.size) for l in jax.tree_util.tree_leaves(abs_params)
    )
    return compiled, n_params, cfg, time.perf_counter() - t0


def _flash_train_flops(cfg_kw, L, B, remat):
    """Analytic FLOPs executed INSIDE the flash-attention Pallas kernels
    per train step. XLA's cost_analysis() counts custom calls as ZERO
    flops, which made round-4's no-remat "ceiling" land at an unphysical
    1.149 (hw_vs_model_flops 0.871 — hardware doing fewer FLOPs than the
    model needs is impossible; round-4 verdict #3). The kernel FLOPs are
    exactly computable from the config:

      fwd (causal):  2 matmuls (QK^T, PV) over the lower triangle
                     = 0.5 * 2 * (2 * B * H * L^2 * Dh) = 2*B*L^2*d_model
      bwd kernel:    5 matmuls (recompute P, dV, dP, dQ, dK) = 2.5x fwd
      remat:         jax.checkpoint re-runs the fwd kernel inside bwd

    per layer, times n_layers."""
    fwd = 2.0 * B * L * L * cfg_kw["d_model"]  # causal-halved, all heads
    mult = 1.0 + 2.5 + (1.0 if remat else 0.0)
    return cfg_kw["n_layers"] * fwd * mult


def _ceiling_row(name, dev, cfg_kw, L, B, persist):
    from benchmarks.common import emit, persist_result
    from benchmarks.llama_scaled import _analytic_flops

    peak_flops, hbm_bw = _specs(dev.device_kind)
    rows = {}
    for remat in (True, False):
        key = "remat" if remat else "no_remat"
        try:
            compiled, n_params, cfg, compile_s = _compile_train_step(
                dev, cfg_kw, L, B, use_flash=True, remat=remat
            )
            hw_flops_xla, bytes_acc = _cost(compiled)
            flash_flops = _flash_train_flops(cfg_kw, L, B, remat)
            rows[key] = {
                # total = XLA-counted + the custom-call FLOPs XLA cannot
                # see; the components are recorded so the correction is
                # auditable
                "hw_flops": hw_flops_xla + flash_flops,
                "hw_flops_xla_counted": hw_flops_xla,
                "flash_flops_analytic": flash_flops,
                "bytes_accessed": bytes_acc,
                "memory": _mem(compiled),
                "compile_s": round(compile_s, 1),
                "n_params": n_params,
            }
        except Exception as e:
            rows[key] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
    ok = {k: v for k, v in rows.items() if "hw_flops" in v}
    if not ok:
        rec = emit(name, 0.0, "mfu_ceiling", error="no variant compiled",
                   variants=rows)
        return rec
    model_flops = _analytic_flops(
        next(iter(ok.values()))["n_params"],
        cfg_kw["n_layers"], cfg_kw["d_model"], L, B * L,
    )
    ceilings = {}
    for k, v in ok.items():
        time_lb = max(v["hw_flops"] / peak_flops,
                      v["bytes_accessed"] / hbm_bw)
        ceiling = model_flops / (time_lb * peak_flops)
        row = {
            "mfu_ceiling": round(min(ceiling, 1.0), 4),
            "bound": (
                "compute" if v["hw_flops"] / peak_flops
                >= v["bytes_accessed"] / hbm_bw else "memory"
            ),
            "arithmetic_intensity": round(
                v["hw_flops"] / max(v["bytes_accessed"], 1), 1
            ),
            "hw_vs_model_flops": round(v["hw_flops"] / model_flops, 3),
        }
        if ceiling > 1.0:
            row["clamped_from"] = round(ceiling, 4)
        if v["hw_flops"] < model_flops:
            # a real train step cannot execute fewer hardware FLOPs than
            # the model requires: if this fires, some op's FLOPs are
            # still invisible to the accounting — flag, never publish
            # silently
            row["flops_accounting_hole"] = round(
                1.0 - v["hw_flops"] / model_flops, 3
            )
        ceilings[k] = row
    best = max(c["mfu_ceiling"] for c in ceilings.values())
    rec = emit(
        name,
        best,
        "mfu_ceiling",
        evidence="aot_compile_only",
        device_kind=dev.device_kind,
        peak_bf16_flops=peak_flops,
        peak_source="spec_sheet_nominal",
        hbm_bytes_per_s=hbm_bw,
        model_flops_per_step=model_flops,
        batch=B,
        seq=L,
        ceilings=ceilings,
        variants=rows,
        caveat=(
            "roofline upper bound from XLA cost analysis (flops + bytes "
            "accessed); real MFU sits below it — overlap, dispatch and "
            "non-roofline ops are not modeled. Peak is the spec-sheet "
            "number for the device_kind, the same table measured MFU "
            "rows divide by"
        ),
    )
    if persist:
        persist_result(name, rec)
    return rec


def _flash_matrix(dev):
    """Compile-check every candidate block size for the TPU target."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks.common import emit, persist_result
    from pytorch_distributed_example_tpu.ops.flash_attention import flash_attention

    sharding = SingleDeviceSharding(dev)
    table = {}
    for L, dh in ((512, 64), (1024, 128), (2048, 128)):
        qs = jax.ShapeDtypeStruct((4, L, 8, dh), jnp.bfloat16, sharding=sharding)
        for b in (128, 256, 512):
            if L % b:
                continue
            key = f"L{L}_dh{dh}_b{b}x{b}"
            try:
                t0 = time.perf_counter()

                def fwd(q, k, v, b=b):
                    return flash_attention(
                        q, k, v, causal=True, block_q=b, block_k=b,
                        interpret=False,
                    )

                def train(q, k, v, b=b):
                    return jax.grad(
                        lambda q: fwd(q, k, v, b).astype(jnp.float32).sum()
                    )(q)

                cf = jax.jit(fwd).lower(qs, qs, qs).compile()
                ct = jax.jit(train).lower(qs, qs, qs).compile()
                flops, _ = _cost(ct)
                table[key] = {
                    "ok": True,
                    "compile_s": round(time.perf_counter() - t0, 1),
                    "train_hw_flops": flops,
                }
            except Exception as e:
                table[key] = {
                    "ok": False,
                    "error": f"{type(e).__name__}: {str(e)[:200]}",
                }
    n_ok = sum(1 for v in table.values() if v.get("ok"))
    rec = emit(
        "aot_flash_compile_matrix",
        n_ok,
        "configs_compiled",
        evidence="aot_compile_only",
        device_kind=dev.device_kind,
        table=table,
    )
    if n_ok:
        persist_result("aot_flash_compile_matrix", rec)
    return rec


def _ring_longctx(topo, L_global=65536, B=1, H=8, D=128):
    """Long-context proof: ring attention over the FULL topology at a
    sequence no single chip could hold, compiled by the TPU backend
    with its per-device memory accounting. 64k causal attention dense
    would need an L x L score matrix; the ring schedule keeps one
    (L/W) x (L/W) block live per step and streams KV around the ICI
    ring (parallel/context_parallel.py ring_attention)."""
    import numpy as np_
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.common import emit, persist_result
    from pytorch_distributed_example_tpu._compat import shard_map_fn
    from pytorch_distributed_example_tpu.parallel.context_parallel import (
        ring_attention,
    )

    devs = list(topo.devices)
    mesh = Mesh(np_.array(devs), ("sp",))
    spec = P(None, "sp", None, None)
    fn = shard_map_fn(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp", causal=True),
        mesh=mesh,
        in_specs=spec,
        out_specs=spec,
    )
    qs = jax.ShapeDtypeStruct(
        (B, L_global, H, D), jnp.bfloat16,
        sharding=NamedSharding(mesh, spec),
    )
    key = f"aot_ring_attention_{L_global >> 10}k"
    try:
        t0 = time.time()
        compiled = jax.jit(fn).lower(qs, qs, qs).compile()
        compile_s = time.time() - t0
    except Exception as e:
        emit(key, 0.0, "GB/device",
             error=f"{type(e).__name__}: {str(e)[:300]}")
        return
    mem = _mem(compiled)
    flops_xla, bytes_acc = _cost(compiled)
    # XLA counts Pallas custom calls as ZERO flops (the _ceiling_row
    # pitfall); when the ring's local block is the flash kernel, the
    # analytic count is the honest number: causal global attention fwd
    # = 2 matmuls over the lower triangle = 2 * B * H * Lg^2 * D.
    flops_analytic = 2.0 * B * H * float(L_global) ** 2 * D
    total = mem["argument_size_in_bytes"] + mem["temp_size_in_bytes"]
    rec = emit(
        key,
        round(total / 1e9, 3),
        "GB/device",
        evidence="aot_compile_only",
        seq_global=L_global,
        seq_per_device=L_global // len(devs),
        n_devices=len(devs),
        heads=H,
        head_dim=D,
        hw_flops_xla_counted=flops_xla,
        fwd_flops_analytic=flops_analytic,
        flops_note=(
            "cost_analysis counts pallas custom calls as zero; when the "
            "local block lowers to the flash kernel, fwd_flops_analytic "
            "is the real work"
        ),
        memory=mem,
        compile_s=round(compile_s, 1),
        fits_16gb_hbm=bool(total < 16e9),
        device_kind=devs[0].device_kind,
    )
    persist_result(key, rec)


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")
    os.environ["TDX_FLASH_INTERPRET"] = "0"  # Mosaic path for the TPU target

    from jax.experimental import topologies

    topo = topologies.get_topology_desc(
        platform="tpu",
        topology_name=os.environ.get("TDX_AOT_TOPO_FULL", "v5e:2x4"),
    )
    dev = _single_device()
    from benchmarks.llama_scaled import CFG_1B

    _flash_matrix(dev)
    # headline MFU geometry (bench.py): 512d/8L/8h @ L=512 B=8
    _ceiling_row("aot_ceiling_headline_mfu", dev, headline_cfg(), 512, 8,
                 persist=True)
    # ~1B single-chip config (llama_scaled --mode mfu): L=1024 B=8
    _ceiling_row("aot_ceiling_llama1b_mfu", dev, CFG_1B, 1024, 8, persist=True)
    # long-context: 64k causal ring attention over the 8-chip topology,
    # the 512k flash-block forward, and fwd+bwd TRAIN compiles through
    # the custom ring VJP at 256k/512k/1M
    _ring_longctx(topo)
    _ring_longctx(topo, L_global=524288, B=1, H=16, D=128)
    for L in (262144, 524288, 1048576):
        _ring_train_compile(topo, L_global=L, B=1, H=16, D=128)


def _ring_train_compile(topo, L_global, B=1, H=16, D=128):
    """value_and_grad of flash-block ring attention, AOT-compiled for the
    full topology — generator of the `aot_ring_attention_train_{N}k`
    rows. The backward is the CUSTOM ring VJP (KV re-rotation, O(local)
    residuals, `context_parallel._ring_core_bwd`); letting jax
    reverse-differentiate the forward fori_loop instead saves every ring
    step's KV shards and needs 17.7 GB/device at 256k."""
    import numpy as np_
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.common import emit, persist_result
    from pytorch_distributed_example_tpu._compat import shard_map_fn
    from pytorch_distributed_example_tpu.parallel.context_parallel import (
        ring_attention,
    )

    devs = list(topo.devices)
    mesh = Mesh(np_.array(devs), ("sp",))
    spec = P(None, "sp", None, None)
    fn = shard_map_fn(
        lambda q, k, v: ring_attention(
            q, k, v, axis_name="sp", causal=True, block_kernel="flash"
        ),
        mesh=mesh, in_specs=spec, out_specs=spec,
    )
    g = jax.grad(
        lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).mean(),
        argnums=(0, 1, 2),
    )
    qs = jax.ShapeDtypeStruct(
        (B, L_global, H, D), jnp.bfloat16,
        sharding=NamedSharding(mesh, spec),
    )
    key = f"aot_ring_attention_train_{L_global >> 10}k"
    try:
        t0 = time.time()
        compiled = jax.jit(g).lower(qs, qs, qs).compile()
        compile_s = time.time() - t0
    except Exception as e:
        emit(key, 0.0, "GB/device",
             error=f"{type(e).__name__}: {str(e)[:300]}")
        return
    mem = _mem(compiled)
    total = mem["argument_size_in_bytes"] + mem["temp_size_in_bytes"]
    rec = emit(
        key,
        round(total / 1e9, 3),
        "GB/device",
        evidence="aot_compile_only",
        seq_global=L_global,
        seq_per_device=L_global // len(devs),
        n_devices=len(devs),
        heads=H,
        head_dim=D,
        what=("value_and_grad of flash-block ring attention via the "
              "custom ring VJP (backward re-rotates KV; O(local) "
              "residuals)"),
        memory=mem,
        compile_s=round(compile_s, 1),
        fits_16gb_hbm=bool(total < 16e9),
        device_kind=devs[0].device_kind,
    )
    persist_result(key, rec)


def headline_cfg():
    return dict(vocab_size=32000, d_model=512, n_layers=8, n_heads=8)


if __name__ == "__main__":
    main()
