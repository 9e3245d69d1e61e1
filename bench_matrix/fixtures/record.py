"""Records the small trace the reduction's test reads, on one TPU chip:

    python3 -m bench_matrix.fixtures.record <out_dir>

Two rounds of a bfloat16 matmul and the program's flash kernel (forward and
backward), each inside a harness span, with a pause between the rounds so
the trace holds a known idle gap. Writes `v5e_small.xplane.pb` and, beside
it, `v5e_small.json`: the numbers the reduction gives on it, to be checked
by hand against `xplane.describe` before they are committed as the test's
expectation.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.ops import flash_attention

    from ..context import Context
    from ..reduce import xplane

    if jax.devices()[0].platform != "tpu":
        print("record: needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def work(a, q):
        f = lambda q: flash_attention(q, q, q, causal=True).astype(jnp.float32).sum()
        return (a @ a).sum(), jax.grad(f)(q)

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    q = jnp.ones((1, 1024, 4, 128), jnp.bfloat16)
    jax.block_until_ready(work(a, q))
    tmp = tempfile.mkdtemp(dir=out_dir)
    ctx = Context(seed=0, seconds=0, devices=jax.devices()[:1],
                  t_start=time.perf_counter(), trace_dir=tmp)
    with ctx.tracing():
        for _ in range(2):
            with ctx.span("step dispatch"):
                out = work(a, q)
            with ctx.span("readback"):
                jax.block_until_ready(out)
            time.sleep(0.02)
    src = xplane.find(tmp)
    dst = os.path.join(out_dir, "v5e_small.xplane.pb")
    shutil.copyfile(src, dst)
    shutil.rmtree(tmp)
    print(xplane.describe(dst))
    t = xplane.load(dst)
    b = xplane.busy(t)
    dev = sorted(t.devices)[0]
    pattern = "tpu_custom_call$"
    k = xplane.time_matching(t, pattern)[dev]
    want = {
        "devices": sorted(t.devices), "window_s": b["window_s"],
        "busy_s": b["busy_s"][dev], "kernel_pattern": pattern,
        "kernel_events": k["events"], "kernel_s": k["seconds"],
        "spans": sorted({s[0] for s in t.spans}),
        "top_op": xplane.top_ops(t, 3)[0][0],
        "idle_per_dispatch_s": xplane.idle_per_span(t, "step dispatch"),
        "read_by_hand": "fill in from the listing above before committing",
    }
    with open(os.path.join(out_dir, "v5e_small.json"), "w") as f:
        json.dump(want, f, indent=1)
    print(json.dumps(want))
    print("size", os.path.getsize(dst))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
