"""The benchmark's command: one cell per run.

    python3 -m bench_matrix.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
`correct`, `attempted`, `failed`, `metrics` and `device` (and `breakdown`
with `--trace 1`). With `--trace 0` the metrics are the cell's end-to-end
metrics; with `--trace 1` the run also traces a short steady slice after
the window and the metrics are the cell's per-layer metrics. No chip, no
number: the command fails off-TPU and on a device missing from
`peaks.json`, and no environment variable selects a size.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python can read it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from . import spec  # noqa: E402
from .context import CompileCounter, Context  # noqa: E402
from .readers import ReadEnv  # noqa: E402

# inside the checkout and listed in .gitignore; a fixed path
OUT_DIR = os.path.join(os.path.dirname(spec.ROOT), ".bench_matrix_out")


def peaks_for(device_kind: str) -> dict:
    with open(spec.ROOT / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise SystemExit(
            f"device kind {device_kind!r} is not in bench_matrix/peaks.json "
            f"({sorted(table)}): no peak, no number"
        )
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks, default=0)


def execute(cell: dict, ctx: Context, peaks: dict, device: dict) -> dict:
    """Run the cell and build the result object. `main` calls this on a
    TPU; the tests call it on the CPU with a tiny cell made in the test."""
    from .reduce import xplane

    result = spec.module("runners", cell["runner"]).run(cell, ctx)
    if ctx.samples_path:
        os.makedirs(os.path.dirname(ctx.samples_path), exist_ok=True)
        with open(ctx.samples_path, "w") as f:
            json.dump(result.samples, f)
    device = dict(device, memory_peak_bytes=memory_peak_bytes(ctx.devices))
    line = {
        "correct": bool(result.correct), "attempted": int(result.attempted),
        "failed": int(result.failed), "device": device,
    }
    units = cell.get("units", {})
    if not ctx.trace_dir:
        missing = [m for m in cell["end_to_end"] if m not in result.metrics]
        if missing:
            raise spec.SpecError(
                f"runner {cell['runner']!r} does not report {missing}"
            )
        line["metrics"] = {
            m: {"value": result.metrics[m], "unit": units.get(m, "")}
            for m in cell["end_to_end"]
        }
        return line

    path = xplane.find(ctx.trace_dir)
    trace = xplane.load(path)
    ctx.say(f"trace: {path} ({os.path.getsize(path)} bytes), devices "
            f"{sorted(trace.devices)}, {len(trace.spans)} harness spans")
    busy = xplane.busy(trace)
    ctx.say("programs in the slice [name, device seconds, runs]: "
            + json.dumps(xplane.module_seconds(trace)))
    env = ReadEnv(
        cell=cell, samples=result.samples, trace=trace, peaks=peaks,
        chips=cell["chips"], memory_peak_bytes=device["memory_peak_bytes"],
        say=ctx.say,
    )
    metrics = {}
    for name, m in cell["per_layer"].items():
        value = spec.module("readers", m["reader"]).read(m["args"], env)
        if value is not None:
            metrics[name] = {"value": value, "unit": m["unit"]}
    line["metrics"] = metrics
    if not busy["busy_s"]:
        raise RuntimeError("the traced slice holds no device operation")
    device["busy_s"] = sum(busy["busy_s"].values()) / len(busy["busy_s"])
    device["window_s"] = busy["window_s"]
    line["breakdown"] = {
        "device_ops": xplane.top_ops(trace, 10),
        "idle_gaps": xplane.idle_gaps(trace, 5),
    }
    return line


def end_to_end_units() -> dict:
    """name -> unit of every end-to-end metric, from BENCHMARK.json."""
    with open(os.path.join(os.path.dirname(spec.ROOT), "BENCHMARK.json")) as f:
        return {m["name"]: m["unit"] for m in json.load(f)["end_to_end"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    cell["units"] = end_to_end_units()

    import jax

    from pytorch_distributed_example_tpu._compat import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench_matrix: JAX found platform {devices[0].platform!r}, not a "
              "TPU; a number from anything else is not a device number",
              file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench_matrix: cell {cell['name']!r} needs {cell['chips']} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    peaks = peaks_for(devices[0].device_kind)
    cache_dir = enable_compile_cache()
    print(f"jax {jax.__version__}; {len(devices)} x {devices[0].device_kind}, "
          f"reached at {time.perf_counter() - T_START:.1f} s; "
          f"compile cache {cache_dir}; cell {cell['name']} seed {args.seed} "
          f"seconds {args.seconds} trace {args.trace}", flush=True)

    trace_dir = ""
    if args.trace:
        trace_dir = os.path.join(OUT_DIR, "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    ctx = Context(
        seed=args.seed, seconds=args.seconds, devices=devices[:cell["chips"]],
        t_start=T_START, trace_dir=trace_dir, compiles=CompileCounter(),
        samples_path=os.path.join(
            OUT_DIR, "samples", f"{cell['name']}.seed{args.seed}.trace{args.trace}.json"
        ),
    )
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }
    try:
        line = execute(cell, ctx, peaks, device)
    finally:
        ctx.compiles.close()
    print(f"compile requests {ctx.compiles.requests}, persistent-cache hits "
          f"{ctx.compiles.hits}; total {time.perf_counter() - T_START:.1f} s",
          flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
