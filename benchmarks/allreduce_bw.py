"""Collective micro-benchmark — BASELINE.json config #2.

all_reduce / broadcast / scatter / all_gather / reduce_scatter over the
world group, tensor sizes 1KB - 1GB (cap configurable; default 256MB to
stay inside one chip's HBM headroom alongside double-buffering). Reports
algorithm bandwidth (payload/time) and bus bandwidth (ring-traffic model:
allreduce moves 2(W-1)/W bytes per payload byte; one-to-all ops (W-1)/W).

broadcast and scatter lower to source-masked psum (backends/xla.py), so
their wire cost matches an allreduce — the acceptance check here is
broadcast ~= allreduce bandwidth, not W x worse.

`--op quant` is the QUANTIZED-ALL-REDUCE row (ops/quant.py, EQuARX
arxiv 2506.17615): the same payload reduced at `--wire f32`, `bf16`,
and `int8` width. Each row reports the measured payload bandwidth
(payload bytes / wall) AND the analytic per-rank WIRE bytes under the
ring model — on the CPU host, shared-memory collectives don't reward
narrow wires the way ICI does, so the CPU acceptance number is the
wire-bytes accounting (`wire_reduction_x` ≈ 3.9x for int8 at block
256); the measured-bandwidth ratio is the TPU-window claim (≥1.8x
target). Self-persists as `allreduce_quant` on TPU.

Torch-reference equivalent: the gloo ring allreduce the reference's
toy/main.py exercises (SURVEY.md §2.2 N8/N9). Here each collective is one
compiled XLA program over the ICI/host mesh (backends/xla.py).

`--planner` is the TOPOLOGY-AWARE-PLANNER row (plan/, ISSUE 9): the same
public all_reduce dispatch timed stock vs planner-enabled per sweep
size, with the winning algorithm chosen from the measured probe table
(persisted on disk keyed by topology; `--no-probe-cache` bypasses).
Self-persists as `allreduce_planner` on TPU.

`--planner --plane-pipeline` additionally A/Bs the p2p-plane EXECUTOR
variants (ISSUE 10 satellite): every plane candidate — ring, rhd, and
the chunk-pipelined `ring_pipe` (executor.py: send of chunk i+1
overlaps the fold of chunk i) — timed over a real in-process plane gang
of `--plane-world` ranks per sweep size, with the measured timings
written into the probe cache's PLANE rows (same topology key a
multiproc gang of that shape detects), so `_agreed_plane_choice` picks
the pipelined walk only where it measured fastest. Self-persists as
`plan_pipeline` on TPU.

Usage: python benchmarks/allreduce_bw.py [--max-mb 256] [--op all_reduce]
       python benchmarks/allreduce_bw.py --op quant [--wire int8]
       python benchmarks/allreduce_bw.py --planner [--no-probe-cache]
       python benchmarks/allreduce_bw.py --planner --plane-pipeline
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

OPS = [
    "all_reduce",
    "broadcast",
    "scatter",
    "all_gather",
    "reduce_scatter",
    "send_recv",
]


WIRES = ["f32", "bf16", "int8"]


def run_quant(args, tdx, W):
    """The `--op quant` sweep: one jitted shard_map program per
    (size, wire) reducing a rank-stacked (W, n) f32 payload to its mean
    — f32 via plain pmean, bf16 via the cast-reduce-cast compress
    lowering, int8 via `ops.quant.quantized_all_reduce` (wire-width in
    both collective phases). Rows carry measured bandwidth + analytic
    wire bytes; the summary row is the acceptance record."""
    import time as _time

    import jax
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from benchmarks.common import device_sync, emit, on_tpu, persist_result
    from pytorch_distributed_example_tpu._compat import shard_map_fn
    from pytorch_distributed_example_tpu.backends.xla import AXIS
    from pytorch_distributed_example_tpu.ops.quant import (
        DEFAULT_BLOCK_SIZE,
        allreduce_wire_bytes,
        quantized_all_reduce,
    )

    g = tdx.distributed._resolve(None)
    mesh = g.backend_impl.mesh.jax_mesh
    wires = WIRES if args.wire == "all" else [args.wire]
    if "f32" not in wires:
        wires = ["f32"] + wires  # every ratio is vs the f32 row

    def body_for(wire):
        if wire == "f32":
            return lambda r: lax.pmean(r, AXIS)
        if wire == "bf16":
            import jax.numpy as jnp

            return lambda r: lax.pmean(
                r.astype(jnp.bfloat16), AXIS
            ).astype(r.dtype)
        return lambda r: quantized_all_reduce(
            r, AXIS, wire=wire, block_size=DEFAULT_BLOCK_SIZE, mean=True
        )

    size = int(args.min_kb * 1024)
    max_size = int(args.max_mb * 1024 * 1024)
    rows, best = [], None
    while size <= max_size:
        n = max(size // 4, 1)  # fp32 elements per rank
        gen = np.random.default_rng(0)
        x = np.tile(gen.standard_normal(n).astype(np.float32), (W, 1))
        per_wire = {}
        for wire in wires:
            prog = jax.jit(
                shard_map_fn(
                    body_for(wire), mesh=mesh,
                    in_specs=P(AXIS), out_specs=P(AXIS),
                )
            )
            out = None
            for _ in range(max(args.warmup, 1)):
                out = prog(x)
            device_sync(out)
            t0 = _time.perf_counter()
            for _ in range(args.iters):
                out = prog(x)
            device_sync(out)
            dt = (_time.perf_counter() - t0) / args.iters
            wire_bytes = allreduce_wire_bytes(
                n, W, wire, DEFAULT_BLOCK_SIZE
            )
            per_wire[wire] = (dt, wire_bytes)
            f32_dt, f32_wire = per_wire["f32"]
            rec = emit(
                f"allreduce_quant_{wire}_{_fmt(size)}",
                size / dt / 1e9,
                "GB/s",
                wire=wire,
                bytes=size,
                world=W,
                us=round(dt * 1e6, 1),
                wire_bytes_per_rank=wire_bytes,
                wire_reduction_x=round(f32_wire / max(wire_bytes, 1), 3),
                measured_x_vs_f32=round(f32_dt / dt, 3),
            )
            rows.append(rec)
            if wire == "int8" and (
                best is None or rec["value"] > best["value"]
            ):
                best = rec
        size *= 4
    # a world-1 mesh has no wire (every wire_reduction_x is 0) and a
    # sweep without the int8 row has no acceptance subject — both would
    # record value 0.0 against the 1.5x target, reading as a failure
    # (and, persisted, clobbering a real measurement); mark them
    # degenerate instead and never persist one
    degenerate = None
    if W <= 1:
        degenerate = "world=1: no inter-device wire to account"
    elif best is None:
        degenerate = "int8 row not in sweep (--wire)"
    if degenerate:
        print(
            f"[allreduce_quant] degenerate run ({degenerate}); summary "
            "is not an acceptance record and will not be persisted",
            file=sys.stderr,
        )
    summary = emit(
        "allreduce_quant_summary",
        best["wire_reduction_x"] if best and not degenerate else 0.0,
        "x_wire_bytes",
        best_int8_measured_x_vs_f32=(
            best["measured_x_vs_f32"] if best else 0.0
        ),
        best_int8_row=best["metric"] if best else "",
        target_wire_accounting=1.5,
        target_tpu_measured=1.8,
        world=W,
        block_size=DEFAULT_BLOCK_SIZE,
        degenerate=degenerate or "",
        rows=rows,
    )
    if on_tpu() and not degenerate:
        persist_result("allreduce_quant", summary)
    return rows


def run_planner(args, tdx, W):
    """The `--planner` A/B (ISSUE 9): the SAME public `tdx.all_reduce`
    dispatch timed with the topology-aware planner off (stock psum
    lowering) and on (probe-chosen schedule per size bucket), per sweep
    size. The winning algorithm comes from the measured probe table —
    when "onepass" wins a bucket the planner dispatches the stock
    lowering and the ratio honestly reads ~1.0x. Summary value is the
    best planner/stock ratio over sizes where a SYNTHESIZED schedule
    was chosen; the acceptance target is >= 1.3x for at least one
    (size, world) regime."""
    import time as _time

    import numpy as np

    from benchmarks.common import device_sync, emit, on_tpu, persist_result
    from pytorch_distributed_example_tpu import plan

    g = tdx.distributed._resolve(None)
    if W <= 1:
        # single visible device: nothing to plan over — emit the
        # degenerate summary instead of tripping over an empty
        # candidate set inside the sweep
        print(
            "[allreduce_planner] degenerate run (world=1: nothing to "
            "plan over); summary is not an acceptance record",
            file=sys.stderr,
        )
        return [emit(
            "allreduce_planner_summary", 0.0, "x_vs_stock",
            target=1.3, world=W, degenerate="world=1: nothing to plan over",
        )]
    if args.no_probe_cache:
        os.environ["TDX_PLANNER_PROBE_CACHE"] = ""
        plan.reset_group(g)

    def timed(run):
        out = None
        for _ in range(max(args.warmup, 1)):
            out = run()
        device_sync(out)
        t0 = _time.perf_counter()
        for _ in range(args.iters):
            out = run()
        device_sync(out)
        return (_time.perf_counter() - t0) / args.iters

    size = int(args.min_kb * 1024)
    max_size = int(args.max_mb * 1024 * 1024)
    rows, best = [], None
    while size <= max_size:
        n = max(size // 4, 1)
        flat = tdx.DistTensor.from_rank_fn(
            lambda r: np.full((n,), float(r), np.float32)
        )

        def run():
            tdx.all_reduce(flat)
            return flat

        plan.enable_for_group(g, False)
        dt_stock = timed(run)
        plan.enable_for_group(g, True)
        dt_plan = timed(run)  # first call probes + compiles; warmup absorbs
        # report the choice for the plane the timed dispatch actually
        # took (multiproc gangs lower onto the p2p plane, not XLA)
        plane = (
            "plane"
            if tdx.distributed._world.mode == "multiproc"
            else "driver"
        )
        choice = plan.planner_for_group(g).explain(
            "all_reduce", size, plane=plane
        )
        plan.enable_for_group(g, False)
        speedup = dt_stock / dt_plan if dt_plan > 0 else 0.0
        rec = emit(
            f"allreduce_planner_{_fmt(size)}",
            size / dt_plan / 1e9,
            "GB/s",
            bytes=size,
            world=W,
            us=round(dt_plan * 1e6, 1),
            stock_us=round(dt_stock * 1e6, 1),
            speedup_x=round(speedup, 3),
            algorithm=choice["algorithm"],
            source=choice["source"],
            probe_timings=choice["timings"],
        )
        rows.append(rec)
        if choice["algorithm"] != "onepass" and (
            best is None or rec["speedup_x"] > best["speedup_x"]
        ):
            best = rec
        size *= 4
    degenerate = None
    if best is None:
        degenerate = "probe table chose the stock lowering at every size"
    if degenerate:
        print(
            f"[allreduce_planner] degenerate run ({degenerate}); summary "
            "is not an acceptance record and will not be persisted",
            file=sys.stderr,
        )
    summary = emit(
        "allreduce_planner_summary",
        best["speedup_x"] if best and not degenerate else 0.0,
        "x_vs_stock",
        best_row=best["metric"] if best else "",
        best_algorithm=best["algorithm"] if best else "",
        choice_source=best["source"] if best else "",
        target=1.3,
        world=W,
        topology=choice["topology"],
        degenerate=degenerate or "",
        rows=rows,
    )
    if on_tpu() and not degenerate:
        persist_result("allreduce_planner", summary)
    return rows


def run_plane_pipeline(args, tdx):
    """The `--planner --plane-pipeline` A/B: time EVERY p2p-plane
    all_reduce candidate (ring / rhd / chunk-pipelined ring_pipe) over a
    real in-process plane gang per sweep size, and merge the measured
    timings into the probe cache's plane rows — the honest route for the
    probe table to pick (or reject) the pipelined executor walk. CPU
    acceptance = bitwise result parity + a complete measured row set;
    the speedup summary is the TPU-host/multi-host claim (>= 1.1x
    target where the fold can hide wire time)."""
    import threading
    import time as _time

    import numpy as np

    from benchmarks.common import emit, on_tpu, persist_result
    from pytorch_distributed_example_tpu.plan import (
        executor, probe, schedules,
    )
    from pytorch_distributed_example_tpu.plan.planner import (
        CollectivePlanner,
    )
    from pytorch_distributed_example_tpu.plan.topology import Topology
    from pytorch_distributed_example_tpu.p2p import P2PPlane
    from pytorch_distributed_example_tpu.store import HashStore

    W = max(int(args.plane_world), 2)
    topo = Topology(W, (tuple(range(W)),), "cpu")
    pl = CollectivePlanner(topo, cache=probe.ProbeCache(
        None if not args.no_probe_cache else ""
    ))
    cands = pl.candidates("all_reduce", "sum", "plane")
    pipe_chunks = executor.default_pipeline_chunks()

    store = HashStore(60.0)
    planes = [
        P2PPlane(r, store, advertise="127.0.0.1").start() for r in range(W)
    ]
    try:
        size = int(args.min_kb * 1024)
        max_size = int(args.max_mb * 1024 * 1024)
        rows, best = [], None
        while size <= max_size:
            n = max(size // 4, W)
            gen = np.random.default_rng(0)
            xs = [
                gen.standard_normal(n).astype(np.float32) for _ in range(W)
            ]
            timings, outs = {}, {}

            def gang(alg, route, iters=None):
                iters = args.iters if iters is None else iters
                plan = pl.plan_for("all_reduce", alg, n)
                pipe = (
                    pipe_chunks if alg in schedules.EXEC_VARIANTS else 1
                )
                res = [None] * W
                errs = [None] * W

                def worker(r):
                    try:
                        for i in range(iters):
                            res[r] = executor.execute(
                                plan, r, xs[r], planes[r],
                                route=f"{route}/{i}", timeout=30.0,
                                pipeline_chunks=pipe,
                            )
                    except Exception as e:  # noqa: BLE001 — bench records
                        errs[r] = e
                ts = [
                    threading.Thread(target=worker, args=(r,))
                    for r in range(W)
                ]
                t0 = _time.perf_counter()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join(120.0)
                dt = (_time.perf_counter() - t0) / iters
                if any(t.is_alive() for t in ts):
                    # a hung rank must not masquerade as a (terrible)
                    # measurement — and must never reach the probe cache
                    raise RuntimeError(
                        f"plane gang hung at {alg} {size}B (thread alive "
                        "after 120s join)"
                    )
                if any(errs):
                    raise RuntimeError(f"plane gang failed: {errs}")
                return dt, res[0]

            for alg in cands:
                # one warm iteration: connections + plan synthesis
                gang(alg, f"ppw/{size}/{alg}", iters=1)
                timings[alg], outs[alg] = gang(alg, f"pp/{size}/{alg}")
            # an execution VARIANT must be bitwise-identical to its base
            # (same schedule, same fold order); different ALGORITHMS
            # legitimately differ in reduction order (allclose only)
            for alg, out in outs.items():
                base = schedules.EXEC_VARIANTS.get(alg)
                if base is not None:
                    assert out.tobytes() == outs[base].tobytes(), (
                        f"{alg} result diverged bitwise from {base} at "
                        f"{size}B"
                    )
                else:
                    np.testing.assert_allclose(
                        out, outs["ring"], rtol=1e-5, atol=1e-5
                    )
            if not args.no_probe_cache:
                pl.cache.update(
                    topo.key(), "all_reduce", probe.bucket_bytes(size),
                    timings, plane="plane",
                )
            speed = timings["ring"] / timings["ring_pipe"]
            rec = emit(
                f"plan_pipeline_{_fmt(size)}",
                size / timings["ring_pipe"] / 1e9,
                "GB/s",
                bytes=size,
                world=W,
                pipeline_chunks=pipe_chunks,
                us={a: round(t * 1e6, 1) for a, t in timings.items()},
                ring_pipe_x_vs_ring=round(speed, 3),
                winner=min(timings, key=timings.get),
            )
            rows.append(rec)
            if best is None or rec["ring_pipe_x_vs_ring"] > best[
                "ring_pipe_x_vs_ring"
            ]:
                best = rec
            size *= 4
    finally:
        for p in planes:
            p.close()
    summary = emit(
        "plan_pipeline_summary",
        best["ring_pipe_x_vs_ring"] if best else 0.0,
        "x_vs_ring",
        best_row=best["metric"] if best else "",
        world=W,
        # CPU acceptance is the honest A/B itself: bitwise variant
        # parity + a complete measured candidate set in the cache (the
        # table may well KEEP the plain walk — on a loaded loopback
        # host the extra frames usually lose). The >= 1.1x speedup is
        # the real-wire (TPU-host / multi-host) claim.
        target_multihost=1.1,
        cached=not args.no_probe_cache,
        candidates=list(cands),
        rows=rows,
    )
    if on_tpu() and best:
        persist_result("plan_pipeline", summary)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-mb", type=float, default=256.0)
    ap.add_argument("--min-kb", type=float, default=1.0)
    ap.add_argument(
        "--op", choices=OPS + ["both", "all", "quant"], default="both"
    )
    ap.add_argument(
        "--wire", choices=WIRES + ["all"], default="all",
        help="--op quant: which wire widths to sweep (f32 always runs "
        "as the ratio base)",
    )
    ap.add_argument(
        "--planner", action="store_true",
        help="A/B the topology-aware collective planner vs the stock "
        "lowering over the sweep (probe-chosen algorithms)",
    )
    ap.add_argument(
        "--no-probe-cache", action="store_true",
        help="--planner: ignore and do not write the on-disk probe "
        "cache (sets TDX_PLANNER_PROBE_CACHE='')",
    )
    ap.add_argument(
        "--plane-pipeline", action="store_true",
        help="--planner: A/B the p2p-plane executor variants (ring vs "
        "chunk-pipelined ring_pipe) over an in-process plane gang and "
        "feed the measured timings to the probe cache's plane rows",
    )
    ap.add_argument(
        "--plane-world", type=int, default=4,
        help="--plane-pipeline: gang size for the in-process plane A/B",
    )
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=5)
    args = ap.parse_args()

    import numpy as np

    import pytorch_distributed_example_tpu as tdx

    from benchmarks.common import device_sync, emit

    if args.planner and args.plane_pipeline:
        # plane-executor A/B: no device mesh involved — pure p2p plane
        return run_plane_pipeline(args, tdx)

    if not tdx.is_initialized():
        tdx.init_process_group(backend="xla")
    W = tdx.get_world_size()

    if args.planner:
        return run_planner(args, tdx, W)

    if args.op == "quant":
        return run_quant(args, tdx, W)

    if args.op == "both":  # headline trio: reduce, one-to-all, p2p
        ops = ["all_reduce", "broadcast", "send_recv"]
    elif args.op == "all":
        ops = OPS
    else:
        ops = [args.op]

    size = int(args.min_kb * 1024)
    max_size = int(args.max_mb * 1024 * 1024)
    results = []
    while size <= max_size:
        n = max(size // 4, 1)  # fp32 elements per rank
        flat = tdx.DistTensor.from_rank_fn(
            lambda r: np.full((n,), float(r), np.float32)
        )
        # chunk-list input for scatter / reduce_scatter: W rows of n/W elems
        nc = max(n // W, 1)
        rows = tdx.DistTensor.from_rank_fn(
            lambda r: np.full((W, nc), float(r), np.float32)
        )
        for op in ops:
            if op == "all_reduce":
                run = lambda: (tdx.all_reduce(flat), flat)[1]
                bus_factor = 2 * (W - 1) / W
            elif op == "broadcast":
                run = lambda: (tdx.broadcast(flat, 0), flat)[1]
                bus_factor = (W - 1) / W
            elif op == "scatter":
                run = lambda: tdx.scatter(rows, 0)
                bus_factor = (W - 1) / W
            elif op == "all_gather":
                run = lambda: tdx.all_gather(flat)
                bus_factor = (W - 1) / W
            elif op == "send_recv":
                # p2p data plane (round-2 VERDICT #5): a full ring of
                # paired send/recv — ONE lax.ppermute over the mesh, the
                # device-to-device route for same-mesh transfers. Every
                # rank ships the whole payload one hop, so algbw is
                # directly comparable to broadcast's.
                def run():
                    ops = []
                    for r in range(W):
                        ops.append(
                            tdx.P2POp(tdx.isend, flat, (r + 1) % W, rank=r)
                        )
                        ops.append(
                            tdx.P2POp(tdx.irecv, flat, (r - 1) % W, rank=r)
                        )
                    for w in tdx.batch_isend_irecv(ops):
                        w.wait()
                    return flat

                bus_factor = 1.0
            else:  # reduce_scatter
                run = lambda: tdx.reduce_scatter(rows)
                bus_factor = (W - 1) / W
            out = None
            for _ in range(args.warmup):
                out = run()
            if out is None:  # --warmup 0: still need one compile pass
                out = run()
            device_sync(out)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = run()
            device_sync(out)
            dt = (time.perf_counter() - t0) / args.iters
            payload = (
                size
                if op in ("all_reduce", "broadcast", "all_gather", "send_recv")
                else nc * W * 4
            )
            algbw = payload / dt / 1e9
            results.append(
                emit(
                    f"{op}_bw_{_fmt(size)}",
                    algbw,
                    "GB/s",
                    bus_bw=round(algbw * bus_factor, 3),
                    bytes=payload,
                    world=W,
                    us=round(dt * 1e6, 1),
                )
            )
        size *= 4
    emit("collective_bw_summary", len(results), "rows", rows=results)
    return results


def _fmt(size: int) -> str:
    if size >= 1 << 20:
        return f"{size >> 20}MB"
    return f"{size >> 10}KB"


if __name__ == "__main__":
    main()
