"""Run every benchmark, collect the JSON lines, write one results file.

    python benchmarks/run_all.py [--out chiprun_out/bench_results.json] [--quick]

Each bench runs in its OWN subprocess with a timeout — a crash in one
config cannot take down the sweep — and the last JSON line of its stdout
is recorded (with rc/stderr tail on failure). One process owns the chip
at a time, and this parent never touches JAX, so the children can. The
headline `bench.py` (DDP MNIST + MFU) runs first; `--quick` shrinks steps
for a fast smoke sweep. The exit code is nonzero if ANY bench failed.
ROADMAP S1 replaces this sweep with the cell matrix.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# --cpu: an N-device virtual CPU mesh in each subprocess (smoke runs / CI).
# `jax_num_cpu_devices` has no environment form, so a preamble sets it
# before the bench's first backend touch.
_CPU_PIN = (
    "import os, sys, runpy, jax\n"
    "jax.config.update('jax_platforms', 'cpu')\n"
    "jax.config.update('jax_num_cpu_devices',\n"
    "                  int(os.environ.get('TDX_CPU_DEVICES', '8')))\n"
    # hardware-rate matmuls stated outright, as benchmarks/common.
    # pin_numerics does for benches that call it themselves
    "jax.config.update('jax_default_matmul_precision', 'default')\n"
    "sys.argv = sys.argv[1:]\n"
    "runpy.run_path(sys.argv[0], run_name='__main__')\n"
)

# jobs that measure the chip and exit nonzero without one; --cpu leaves
# them out rather than recording a failure
_CHIP_ONLY = ("headline", "llama_scaled_mfu")


def _jobs(quick: bool):
    q = quick
    headline_env = (
        {
            "BENCH_STEPS": "20",
            "BENCH_WARMUP": "5",
            "BENCH_MFU_STEPS": "3",
            "BENCH_MFU_WARMUP": "1",
        }
        if q
        else {}
    )
    return [
        ("headline", [sys.executable, "bench.py"], headline_env),
        (
            "allreduce_bw",
            [sys.executable, "benchmarks/allreduce_bw.py"]
            + (["--max-mb", "1", "--iters", "3", "--warmup", "1"] if q else []),
            {},
        ),
        (
            # quantized all-reduce wire rows (ISSUE 7): f32/bf16/int8
            # payload bandwidth + analytic wire bytes; CPU acceptance is
            # the wire-bytes accounting, TPU the measured ratio
            "allreduce_quant",
            [sys.executable, "benchmarks/allreduce_bw.py", "--op", "quant"]
            + (
                ["--max-mb", "1", "--iters", "3", "--warmup", "1"]
                if q
                else ["--max-mb", "64"]
            ),
            {},
        ),
        (
            # topology-aware collective planner vs stock lowering
            # (ISSUE 9): same dispatch A/B'd per size, algorithm chosen
            # by the measured probe table; >= 1.3x target in at least
            # one (size, world) regime
            "allreduce_planner",
            [sys.executable, "benchmarks/allreduce_bw.py", "--planner"]
            + (
                # quick: hermetic (no cache reads/writes), just the
                # crossover buckets; full: the real artifact flow
                ["--no-probe-cache", "--min-kb", "256", "--max-mb", "4",
                 "--iters", "3", "--warmup", "1"]
                if q
                else ["--max-mb", "64"]
            ),
            {},
        ),
        (
            # p2p-plane executor variants A/B (ISSUE 10 satellite): ring
            # vs chunk-pipelined ring_pipe over a real in-process plane
            # gang; measured timings land in the probe cache's plane
            # rows (hermetic in quick mode)
            "plan_pipeline",
            [sys.executable, "benchmarks/allreduce_bw.py", "--planner",
             "--plane-pipeline"]
            + (
                ["--no-probe-cache", "--min-kb", "64", "--max-mb", "1",
                 "--iters", "3"]
                if q
                else ["--min-kb", "64", "--max-mb", "16", "--iters", "5"]
            ),
            {},
        ),
        (
            # ZeRO weight-update sharding capability headline (ISSUE 10):
            # a transformer-LM whose unsharded optimizer state exceeds
            # the per-rank budget trains under shard_weight_update=auto;
            # >= 1.8x measured opt-state reduction at world 2
            "zero_auto_mem",
            [sys.executable, "benchmarks/zero_bench.py", "--mode", "mem"]
            + (["--quick", "--steps", "2"] if q else ["--steps", "4"]),
            {"TDX_CPU_DEVICES": "2"},  # the world-2 acceptance geometry
        ),
        (
            # ZeRO parity row (ISSUE 10): auto vs off from the same init
            # on ConvNet + transformer-LM; worst rel param diff <= 1e-5
            # (measures bitwise on CPU)
            "zero_auto_parity",
            [sys.executable, "benchmarks/zero_bench.py", "--mode",
             "parity"]
            + (["--quick", "--steps", "3"] if q else ["--steps", "6"]),
            {},
        ),
        (
            # trace-time planner on the ZeRO train step (ISSUE 20):
            # stock vs planner-routed compiled step (agreed table
            # lowers the grad reduce-scatter / weight re-gather as ring
            # bodies) plus overlap on/off; --force-alg ring keeps the
            # CPU row's non-stock selection deterministic (TPU probes)
            "zero_planner_traced",
            [sys.executable, "benchmarks/zero_bench.py", "--mode",
             "plan", "--force-alg", "ring"]
            + (["--quick", "--steps", "3"] if q else ["--steps", "6"]),
            {"TDX_CPU_DEVICES": "2"},
        ),
        (
            "resnet_ddp",
            [sys.executable, "benchmarks/resnet_ddp.py"]
            + (["--steps", "5", "--warmup", "2", "--batch", "32"] if q else []),
            {},
        ),
        (
            "transformer_lm",
            [sys.executable, "benchmarks/transformer_lm.py"]
            + (
                ["--preset", "small", "--steps", "5", "--warmup", "2"]
                if q
                else ["--bf16"]
            ),
            {},
        ),
        (
            # TP-decode collectives through the traced planner
            # (ISSUE 20): vocab-logits gather + activation
            # gather-matmul, stock vs ring lowering, overlap isolated
            "transformer_tp_decode_planned",
            [sys.executable, "benchmarks/transformer_lm.py",
             "--planner", "traced"]
            + (
                ["--preset", "small", "--steps", "5", "--batch", "4"]
                if q
                else ["--preset", "small", "--steps", "20"]
            ),
            {},
        ),
        (
            "bert_finetune",
            [sys.executable, "benchmarks/bert_finetune.py"]
            + (
                ["--preset", "small", "--steps", "5", "--warmup", "2"]
                if q
                else ["--bf16"]
            ),
            {},
        ),
        (
            "decode",
            [sys.executable, "benchmarks/generate_bench.py"]
            + (
                ["--preset", "small", "--prompt", "32", "--new", "32"]
                if q
                else ["--bf16"]
            ),
            {},
        ),
        (
            # continuous-batching serve engine vs static-batch
            # run-to-completion on the same model/hardware (ISSUE 5):
            # goodput tokens/s + TTFT/TPOT percentiles
            "serve",
            [sys.executable, "benchmarks/serve_bench.py"]
            + (
                ["--preset", "small", "--requests", "24", "--slots", "8"]
                if q
                else ["--bf16"]
            ),
            {},
        ),
        (
            # same bimodal traffic, production context-window
            # provisioning (ISSUE 6): dense pays max_seq per slot, the
            # paged pool pays live tokens — the >= 4x cache-memory row
            "serve_paged_mem",
            [sys.executable, "benchmarks/serve_bench.py", "--max-seq", "512"]
            + (
                ["--preset", "small", "--requests", "24", "--slots", "8"]
                if q
                else ["--bf16"]
            ),
            {},
        ),
        (
            # long-prompt burst + trickling shorts, chunked vs unchunked
            # prefill (ISSUE 6): short-class p99 TTFT bounding
            "serve_longburst",
            [sys.executable, "benchmarks/serve_bench.py", "--trace",
             "longburst"]
            + (
                ["--preset", "small", "--requests", "24", "--slots", "8"]
                if q
                else ["--bf16"]
            ),
            {},
        ),
        (
            # fixed-pool-bytes concurrency, int8 KV vs f32 (ISSUE 7):
            # >= 1.8x admitted-slots target + greedy match-rate floor
            "serve_quant_capacity",
            [sys.executable, "benchmarks/serve_bench.py", "--trace",
             "capacity"]
            + (
                ["--preset", "tiny", "--requests", "16"]
                if q
                else ["--preset", "small", "--requests", "32"]
            ),
            {},
        ),
        (
            # multi-tenant SLO protection under overload (ISSUE 8): gold
            # p99 TTFT <= 1.2x its uncontended value while bronze absorbs
            # explicit sheds, vs FIFO collapse in the baseline
            "serve_multitenant",
            [sys.executable, "benchmarks/serve_bench.py", "--trace",
             "multitenant"]
            + (
                ["--preset", "tiny", "--requests", "24", "--slots", "4"]
                if q
                else ["--preset", "small", "--requests", "48"]
            ),
            {},
        ),
        (
            # kill-mid-traffic recovery (ISSUE 8): checkpoint-every-step
            # + abandon + restore; recovery_time_s row, token identity
            # asserted inside the bench
            "serve_recovery",
            [sys.executable, "benchmarks/serve_bench.py", "--trace",
             "recovery"]
            + (
                ["--preset", "tiny", "--requests", "12", "--slots", "4"]
                if q
                else ["--preset", "small", "--requests", "32"]
            ),
            {},
        ),
        (
            # disaggregated prefill/decode pools (ISSUE 19): decode-step
            # p99 under a long-prompt burst, colocated chunked-prefill
            # engine vs the split pools with live KV migration — TPOT
            # isolation x + the two-pool autoscale trace; token identity
            # asserted inside the bench
            "serve_disagg",
            [sys.executable, "benchmarks/serve_bench.py", "--trace",
             "disagg"]
            + (
                ["--preset", "tiny", "--requests", "12", "--slots", "4"]
                if q
                else ["--preset", "small", "--requests", "24"]
            ),
            {},
        ),
        (
            # prefix-sharing paged KV (ISSUE 12): shared-preamble trace
            # replayed with the radix prefix cache on vs off — >= 3x
            # TTFT target + pool-bytes/request reduction, token
            # identity asserted inside the bench
            "serve_prefix",
            [sys.executable, "benchmarks/serve_prefix.py"]
            + (
                ["--preset", "tiny", "--requests", "12", "--slots", "4",
                 "--preamble-tokens", "64"]
                if q
                else ["--preset", "small", "--bf16"]
            ),
            {},
        ),
        (
            # closed-loop SLO autoscaling under the 10x diurnal
            # open-loop load harness (ISSUE 15): gold attainment >=
            # 0.99 across the swing, chip-seconds saved vs static peak
            # provisioning, chaos-proven token-exact mid-swing resize —
            # hermetic on the virtual clock in both modes
            "serve_autoscale",
            [sys.executable, "benchmarks/load_harness.py"]
            + (
                ["--preset", "tiny", "--duration", "30", "--tenants",
                 "4", "--max-replicas", "4"]
                if q
                else ["--preset", "small"]
            ),
            {},
        ),
        (
            # decision-to-first-token at a NEW gang width, pre-warmed
            # (persistent cache + serialized executables) vs cold
            # compile — the resize-latency row (ISSUE 16, >= 5x)
            "serve_resize",
            [sys.executable, "benchmarks/serve_resize.py"]
            + (
                ["--reps", "1"]
                if q
                else ["--reps", "2", "--d-model", "128", "--layers", "4",
                      "--heads", "8", "--vocab", "256",
                      "--max-seq-len", "64"]
            ),
            {},
        ),
        (
            # tensor-parallel decode goodput scaling 1 -> 2 chips
            # (ISSUE 6, >= 1.7x target on TPU; CPU runs are a virtual-
            # device wiring smoke, not a measurement)
            "serve_tp",
            [sys.executable, "benchmarks/serve_bench.py", "--tp", "2"]
            + (
                ["--preset", "tiny", "--requests", "12", "--slots", "4"]
                if q
                else ["--bf16"]
            ),
            {},
        ),
        (
            "llama_scaled_mfu",
            [sys.executable, "benchmarks/llama_scaled.py", "--mode", "mfu"]
            + (["--steps", "3", "--warmup", "1"] if q else []),
            {},
        ),
        (
            # always pinned to the 8-device CPU mesh (see main loop): this
            # is an AOT memory-analysis dryrun of the 8B layout, never an
            # execution on the bench chip
            "llama_scaled_memory8b",
            [sys.executable, "benchmarks/llama_scaled.py", "--mode", "memory8b"]
            + (["--seq", "512", "--batch", "2"] if q else []),
            # the 8-device layout IS the measurement: an ambient
            # TDX_CPU_DEVICES (the headline knob) must not change it
            {"TDX_CPU_DEVICES": "8"},
        ),
        (
            "trace_evidence",
            [sys.executable, "benchmarks/trace_evidence.py"],
            {},
        ),
        (
            "reducer_dispatch",
            [sys.executable, "benchmarks/reducer_bench.py"]
            + (["--mb", "1", "--iters", "3", "--warmup", "1"] if q else []),
            {},
        ),
        (
            "p2p_store_bw",
            [sys.executable, "benchmarks/p2p_store_bw.py"]
            + (["--sizes-mb", "1", "--iters", "2"] if q else []),
            {},
        ),
        (
            "loader_scaling",
            [sys.executable, "benchmarks/loader_bench.py"]
            + (["--batches", "10"] if q else []),
            {},
        ),
        (
            "p2p_plane_bw",
            [sys.executable, "benchmarks/p2p_plane_bw.py"]
            + (["--sizes-mb", "1", "--iters", "2"] if q else []),
            {},
        ),
        (
            # deviceless TPU-target AOT compile (real TPU memory
            # accounting, no hardware needed) — round-3 VERDICT #6
            "llama_scaled_memory8b_tpu",
            [sys.executable, "benchmarks/llama_scaled.py", "--mode",
             "memory8b", "--target", "tpu"]
            + (["--seq", "512", "--batch", "2"] if q else []),
            {"TDX_CPU_DEVICES": "8"},  # see llama_scaled_memory8b
        ),
        (
            # flash compile matrix + roofline MFU ceilings, also
            # deviceless (round-3 VERDICT #2's ceiling analysis)
            "tpu_aot_check",
            [sys.executable, "benchmarks/tpu_aot_check.py"],
            {},
        ),
    ]


def _last_json_line(text: str):
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/bench_results.json")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--timeout", type=float, default=1800.0, help="per bench")
    ap.add_argument("--only", default=None, help="comma-separated job names")
    ap.add_argument(
        "--cpu",
        action="store_true",
        help="pin the virtual CPU mesh in each bench (smoke runs / CI)",
    )
    args = ap.parse_args()

    jobs = _jobs(args.quick)
    if args.cpu:
        jobs = [j for j in jobs if j[0] not in _CHIP_ONLY]
    if args.only:
        wanted = set(args.only.split(","))
        unknown = wanted - {n for n, _, _ in jobs}
        if unknown:
            ap.error(f"unknown job(s) {sorted(unknown)}; "
                     f"have {[n for n, _, _ in jobs]}")
        jobs = [j for j in jobs if j[0] in wanted]

    out_path = os.path.join(ROOT, args.out)
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)

    # MERGE with prior results: a --only run must not wipe what an
    # earlier run of the same output file gathered
    results = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f).get("results", {})

    def flush(results):
        # rewrite after every job: a late crash/^C keeps finished results
        with open(out_path, "w") as f:
            json.dump(
                {
                    "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
                    "results": results,
                },
                f,
                indent=2,
            )
    for name, argv, env_extra in jobs:
        env = dict(os.environ, **env_extra)
        # memory8b* never touch the chip: the cpu variant runs the virtual
        # mesh; the tpu variant compiles against a DEVICELESS topology
        # (which works from a CPU-pinned process).
        if args.cpu or name.startswith("llama_scaled_memory8b"):
            argv = [sys.executable, "-c", _CPU_PIN] + argv[1:]
        t0 = time.time()
        try:
            # one retry on signal-crash: XLA CPU's HARDCODED 40 s
            # collective-rendezvous abort (rendezvous.cc:127) fires when
            # a loaded small host starves a device thread past the
            # window — transient load, not the bench, is the usual
            # culprit. t0 resets so 'seconds' reflects the attempt that
            # produced the recorded result.
            attempts = 0
            for attempt in range(2):
                attempts += 1
                t0 = time.time()
                r = subprocess.run(
                    argv, cwd=ROOT, env=env, capture_output=True, text=True,
                    timeout=args.timeout,
                )
                if r.returncode >= 0:
                    break
                print(f"[{name}] crashed (rc={r.returncode})"
                      + ("; retrying once" if attempt == 0 else ""),
                      flush=True)
            rec = _last_json_line(r.stdout)
            results[name] = {
                "rc": r.returncode,
                "seconds": round(time.time() - t0, 1),
                "result": rec,
            }
            if attempts > 1:
                results[name]["attempts"] = attempts
            if r.returncode != 0 or rec is None:
                results[name]["stderr_tail"] = r.stderr[-500:]
        except subprocess.TimeoutExpired:
            results[name] = {
                "rc": -1,
                "seconds": round(time.time() - t0, 1),
                "result": None,
                "error": f"timeout > {args.timeout}s",
            }
        status = results[name]
        print(
            f"[{name}] rc={status['rc']} {status['seconds']}s "
            f"{json.dumps(status['result']) if status['result'] else status.get('error', 'NO JSON')}",
            flush=True,
        )
        flush(results)

    print(f"wrote {out_path}")
    ran = {name for name, _, _ in jobs}
    failed = sorted(
        n for n in ran
        if results[n]["rc"] != 0 or results[n]["result"] is None
    )
    print(f"{len(ran) - len(failed)}/{len(ran)} benches produced a metric"
          + (f"; FAILED: {failed}" if failed else ""))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
