"""Shared benchmark harness helpers (JSON-line emission + persistence)."""

from __future__ import annotations

import json
import os
import time


def pin_numerics(matmul_precision: str = "default"):
    """Pin the process's matmul precision EXPLICITLY (ISSUE 18).

    The test harness pins ``jax_default_matmul_precision`` to "highest"
    (bitwise assertions must not depend on the backend's accumulation
    dtype), while a perf harness must measure hardware-rate matmuls —
    so benches pin "default" (the backend's native fast path; there is
    no "fastest" enum value), making the choice explicit instead of
    inherited from whatever the running jax version's default happens
    to be. The PRNG stream is NOT pinned anywhere: tests, benches,
    examples and the chip all draw from the installed default
    (`jax_threefry_partitionable`), so a bench row and a test assertion
    over "the same" workload really are the same workload. Called after
    the backend is up (plain context config, safe post-init)."""
    import jax

    jax.config.update("jax_default_matmul_precision", matmul_precision)


def emit(metric: str, value: float, unit: str, vs_baseline: float = 0.0, **extra):
    rec = {
        "metric": metric,
        "value": round(float(value), 3),
        "unit": unit,
        "vs_baseline": round(float(vs_baseline), 3),
    }
    rec.update(extra)
    print(json.dumps(rec), flush=True)
    return rec


def chain_pretrain(
    model,
    params,
    train_len: int,
    vocab_cap: int = 256,
    steps: int = 300,
    loss_floor: float = 0.01,
    seed: int = 1,
    batch: int = 16,
):
    """Briefly pretrain a `TransformerLM` on the deterministic bigram
    chain ``next = (5 t + 17) mod V`` and return
    ``(params, chain_fn, final_loss)``.

    Shared by the serve capacity bench and the int8-KV parity tests:
    greedy decode on random-init weights argmaxes over near-tied logits
    (top-2 gaps of order 1e-3), so ANY lossy cache — int8, even bf16 —
    flips tokens at ~2%/token there, measuring argmax noise rather than
    cache fidelity. Training to `loss_floor` at the FULL `train_len`
    the caller will decode to (RoPE positions the model never saw stay
    near-tied too) gives the margins a trained model has; a token
    match rate then measures quantization-induced flips, which is the
    claim. `chain_fn(start, length)` regenerates the data stream for
    prompts."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    V = min(model.cfg.vocab_size, vocab_cap)

    def chain(start, length):
        out = np.empty(length, np.int64)
        out[0] = start % V
        for j in range(1, length):
            out[j] = (5 * out[j - 1] + 17) % V
        return out.astype(np.int32)

    opt = optax.adam(1e-2)

    @jax.jit
    def train_step(p, o, b):
        def loss_fn(pp):
            logits = model.apply(pp, b[:, :-1])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, b[:, 1:]
            ).mean()

        l, grads = jax.value_and_grad(loss_fn)(p)
        up, o = opt.update(grads, o, p)
        return optax.apply_updates(p, up), o, l

    rng = np.random.default_rng(seed)
    o, loss = opt.init(params), None
    for _ in range(steps):
        b = np.stack(
            [chain(int(rng.integers(0, V)), train_len) for _ in range(batch)]
        )
        params, o, loss = train_step(params, o, jnp.asarray(b))
        if float(loss) < loss_floor:
            break
    return params, chain, float(loss)


class BwStubGroup:
    """Minimal ProcessGroup stand-in carrying exactly what the p2p
    routing layer (`dist._store_send`/`_store_recv`) and the planner's
    plane executor consult: store, timeout, group name, rank/size, and
    the group↔global rank maps (identity — the stub IS the world).

    Shared by the p2p bandwidth benches (both the parent process and
    the spawned child) and the planner probe harness, which previously
    each carried their own copy-pasted throwaway `class G`.
    """

    def __init__(self, store, rank: int, size: int, name: str = "bw",
                 timeout: float = 120.0):
        self.store = store
        self.timeout = timeout
        self.group_name = name
        self._rank = int(rank)
        self._size = int(size)

    def rank(self) -> int:
        return self._rank

    def size(self) -> int:
        return self._size

    def get_global_rank(self, r: int) -> int:
        return r

    def get_group_rank(self, r: int) -> int:
        return r


def persist_result(name: str, record: dict) -> None:
    """Merge one bench record into chiprun_out/bench_results.json — the
    directory a chip run brings back (git-ignored). Merging preserves
    other benches' entries; a torn file is set aside, not erased."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out_dir = os.path.join(root, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "bench_results.json")
    doc = {"results": {}}
    if os.path.exists(path):
        try:
            with open(path) as f:
                loaded = json.load(f)
            if isinstance(loaded, dict):  # tolerate a foreign file shape
                doc = loaded
        except ValueError:
            # torn write (a killed bench process): keep the bytes for
            # forensics rather than replacing every row with {}
            os.replace(path, path + ".corrupt")
    doc.setdefault("results", {})
    doc["results"][name] = {"rc": 0, "result": record}
    doc["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2)
    os.replace(tmp, path)


def on_tpu() -> bool:
    import jax

    return jax.devices()[0].platform == "tpu"


def device_sync(x) -> float:
    """Timing barrier: wait for every leaf of ``x`` on the device, then
    return element 0 of the first leaf (callers assert finiteness on it).
    Errors from async work (e.g. OOM) surface here."""
    import jax
    import numpy as np

    # unwrap framework DistTensors (not registered as pytrees)
    x = getattr(x, "array", x)
    leaves = [getattr(l, "array", l) for l in jax.tree_util.tree_leaves(x)]
    jax.block_until_ready(leaves)
    first = leaves[0]
    if isinstance(first, jax.Array):
        # one element, sliced on the device from a shard this process
        # holds: the window a caller times must not carry a copy of the
        # whole leaf to the host
        first = first.addressable_shards[0].data
        first = first[(0,) * first.ndim]
    return float(np.asarray(first).ravel()[0])


def measure_rtt(x, reps: int = 3) -> float:
    """Median seconds of a `device_sync` on already-materialized data —
    the fixed per-barrier cost to subtract from short timed windows."""
    device_sync(x)  # drain any queued work first
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        device_sync(x)
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]
