"""chip_smoke.py — the standing proof that the system starts on the chip.

    python chip_smoke.py            # one process, one TPU host

Drives the two things this system exists to do — training steps through
`init_process_group(backend="xla")` + the trainer factories, and requests
through `ServeEngine` — end to end on the TPU, through the entry points a
user calls, at the full width of the largest model the repo has a record
of fitting in 16 GB (d_model 2048, 16 heads x 128, d_ff 5504, vocab 32000,
bf16; depth is the only thing that may be cut, and is printed). Weights
are random, from a seed.

Contract (what the driver checks):

* the first act is to require `jax.devices()[0].platform == "tpu"`; with
  no accelerator it exits nonzero, names what it found, prints no result;
* every phase prints one PASS / FAIL / SKIP(devices=N) line; any FAIL (or
  exception) makes the exit code nonzero and suppresses the result line;
* the last line of stdout, on success only, is one JSON object
  `{"ok": true, "device": {"platform", "kind", "count"}}` as JAX reports it.
  It is printed only when EVERY phase was attempted at the chip preset and
  none failed, so it always means the same run; the one phase that may
  SKIP is four_chip, which runs exactly when `count` >= 4. The line before
  it lists each phase's verdict;
* one process, no network, nothing left running, no number written under
  a metric's name — the wall times printed are set-up and smoke timings.

Builder-side flags: `--require-four-chips` turns the four-chip phase's
SKIP into a FAIL; `--only a,b` runs a subset of phases (to re-run one
phase on a four-chip host without paying for the rest) and therefore
prints no result line. `PRESETS["tiny"]` is the CPU rehearsal size that
`tests/test_chip_smoke.py` hands straight to `run_phases`; main() knows
the chip preset only and never runs off-TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

PRESETS = {
    # a ~1B Llama block's widths; see module docstring
    "chip": dict(
        mnist=dict(batch_per_chip=64, single_steps=24, fused_steps=8),
        lm=dict(
            vocab=32000, d_model=2048, n_heads=16, d_ff=5504, seq=2048,
            depths=(16, 12, 8), batch_per_chip=4, steps=6, lr=1e-3,
        ),
        kernels=(
            dict(shape=(2, 2048, 16, 128), streamed=False),
            dict(shape=(1, 16384, 16, 128), streamed=True),
        ),
        serve=dict(
            slots=8, block_size=16, pool_blocks=1024, chunk=512,
            max_seq_len=2048,
            # remainders mod the 512 chunk land in the 128 or the 512
            # bucket only, so prefill compiles two programs, not six
            prompt_lens=(100, 300, 420, 812, 1000, 1400, 1500),
            new_tokens=(32, 40, 48, 56, 64, 36, 44),
            prefix_len=512, prefix_tail=300, check_len=640,
            quant_prompt_lens=(300, 420, 812, 1400), quant_new_tokens=32,
            # a toy latent-attention layer at widths both latent kernels
            # take (rows of 128 + 64 values, held as 256)
            latent=dict(d_model=512, n_heads=8, q_rank=128, kv_rank=128,
                        nope=64, rope=64, v=64, prompt_len=300, new_tokens=12),
        ),
        four=dict(
            lm_batch=8, lm_meshes=((2, 2), (4, 1)),
            # 4096 keys per shard: past ring_attention's dense-block limit, so
            # each ring step runs the Pallas kernel
            ring=(1, 16384, 16, 128),
            moe=dict(tokens=4096, d=1024, f=2816, experts=8),
            pipe=dict(d=1024, micro=4, mb=8),
        ),
    ),
    "tiny": dict(
        mnist=dict(batch_per_chip=8, single_steps=6, fused_steps=2),
        lm=dict(
            vocab=256, d_model=64, n_heads=4, d_ff=128, seq=64,
            depths=(2,), batch_per_chip=2, steps=4, lr=1e-2,
        ),
        kernels=(
            dict(shape=(1, 128, 2, 16), streamed=None),
        ),
        serve=dict(
            slots=4, block_size=8, pool_blocks=64, chunk=32,
            max_seq_len=128,
            prompt_lens=(10, 20, 40, 70), new_tokens=(4, 5, 6, 4),
            prefix_len=32, prefix_tail=20, check_len=40,
            quant_prompt_lens=(20, 40), quant_new_tokens=4,
            latent=dict(d_model=64, n_heads=4, q_rank=24, kv_rank=32, nope=16,
                        rope=8, v=16, prompt_len=45, new_tokens=4),
        ),
        four=dict(
            lm_batch=4, lm_meshes=((2, 2),),
            ring=(1, 256, 2, 16),
            moe=dict(tokens=64, d=32, f=64, experts=4),
            pipe=dict(d=32, micro=2, mb=4),
        ),
    ),
}


class Skip(Exception):
    """A phase that cannot run for lack of devices; never a PASS."""


class CacheCounters:
    """Persistent-compile-cache traffic, as jax.monitoring reports it."""

    _EVENTS = {
        "/jax/compilation_cache/compile_requests_use_cache": "requests",
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "written",
    }

    def __init__(self):
        import jax.monitoring

        self.counts = {name: 0 for name in self._EVENTS.values()}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        name = self._EVENTS.get(event)
        if name is not None:
            self.counts[name] += 1

    def close(self):
        import jax.monitoring

        jax.monitoring.unregister_event_listener(self._on_event)


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _finite(x) -> bool:
    import numpy as np

    return bool(np.isfinite(np.asarray(x, dtype=np.float64)).all())


def _shard_bytes(tree, devices):
    """Bytes of ``tree``'s own shards on each of ``devices``."""
    import jax

    held = {d.id: 0 for d in devices}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            if shard.device.id in held:
                held[shard.device.id] += shard.data.nbytes
    return [held[d.id] for d in devices]


def _check_all_hold(label, tree, devices, max_fraction=None):
    """Every device of ``devices`` must hold its share of ``tree`` — the
    arrays the section itself produced, so bytes an earlier section left
    behind cannot satisfy it. ``max_fraction`` bounds one device's part of
    the tree's global bytes where the section claims the tree is sharded
    (a replicated tree would read 1.0). The process-wide `bytes_in_use`
    is printed beside it for the record; the CPU rehearsal has none."""
    import jax

    held = _shard_bytes(tree, devices)
    stats = [d.memory_stats() for d in devices]
    in_use = [int(s["bytes_in_use"]) for s in stats] if all(stats) else None
    print(f"  {label}: bytes of this section's arrays per device = {held}; "
          f"bytes_in_use per device = {in_use}")
    _check(all(b > 0 for b in held), f"{label}: a device holds nothing: {held}")
    if max_fraction is not None:
        total = sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))
        _check(max(held) <= max_fraction * total,
               f"{label}: one device holds {max(held)} of {total} bytes, "
               f"more than {max_fraction:.0%}: not sharded")


def _lm(lm, n_layers, seed=0):
    """(model, bf16 params) at the preset's widths — the fit-on-one-chip
    layout: bf16 master weights."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
    )

    cfg = TransformerConfig(
        vocab_size=lm["vocab"], d_model=lm["d_model"], n_layers=n_layers,
        n_heads=lm["n_heads"], d_ff=lm["d_ff"], max_seq_len=lm["seq"],
        dtype=jnp.bfloat16, use_flash=True, remat=True,
    )
    model = TransformerLM(cfg)

    @jax.jit
    def init(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16), params
        )

    return model, init(jax.random.PRNGKey(seed))


def _next_token_loss(logits, y):
    import optax

    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], y[:, 1:]
    ).mean()


def _assert_kernel_path(label, jitted, args):
    """The lowered step must hold the Mosaic custom call on a TPU backend
    (compiled, not interpreted, and `_flash_ok` did not route to dense);
    off-TPU the kernel is interpreted and lowers to plain HLO."""
    import jax

    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if a.committed else None
        ) if isinstance(a, jax.Array) else a,
        args,
    )
    n = jitted.lower(*abstract).as_text().count("tpu_custom_call")
    if jax.default_backend() == "tpu":
        _check(n > 0, f"{label}: no Mosaic custom call in the lowered step")
        print(f"  {label}: attention path = Mosaic kernel "
              f"({n} tpu_custom_call sites in the lowered step)")
    else:
        print(f"  {label}: attention path = Pallas interpreter "
              f"(backend {jax.default_backend()}: rehearsal only)")


def _reference_attention(q, k, v, w):
    """(o, dq, dk, dv) of causal softmax attention in f32 under
    `jax.default_matmul_precision("highest")`, for loss = sum(o * w).
    One head at a time through `ops.reference.dense_attention`, so the
    (L, L) score matrix of a 16k sequence stays at 1 GB."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.ops.reference import dense_attention

    def loss(q, k, v, w):
        o = dense_attention(q, k, v, causal=True)
        return jnp.sum(o * w), o

    @jax.jit
    def head(q, k, v, w):
        (_, o), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True
        )(q, k, v, w)
        return (o,) + grads

    f32 = lambda x: x.astype(jnp.float32)
    outs = []
    with jax.default_matmul_precision("highest"):
        for h in range(q.shape[2]):
            sl = slice(h, h + 1)
            outs.append(head(f32(q[:, :, sl]), f32(k[:, :, sl]),
                             f32(v[:, :, sl]), w[:, :, sl]))
    return tuple(jnp.concatenate(parts, axis=2) for parts in zip(*outs))


def _rel_err(got, want) -> float:
    """max |got - want| over max |want| — one number per tensor."""
    import numpy as np

    got = np.asarray(got, dtype=np.float32)
    want = np.asarray(want, dtype=np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_collectives(preset):
    """Eager c10d collectives on DistTensors over every visible device,
    against numpy — the examples/toy/main.py path."""
    import numpy as np

    import pytorch_distributed_example_tpu as tdx
    from pytorch_distributed_example_tpu.types import ReduceOp

    tdx.init_process_group(backend="xla")
    try:
        W = tdx.get_world_size()
        rows = np.stack([
            np.arange(4, dtype=np.float32) + 10 * r for r in range(W)
        ])

        t = tdx.DistTensor.from_stacked(rows)
        tdx.all_reduce(t, ReduceOp.SUM)
        np.testing.assert_array_equal(
            t.numpy(), np.broadcast_to(rows.sum(0), rows.shape)
        )

        t = tdx.DistTensor.from_stacked(rows)
        tdx.broadcast(t, src=W - 1)
        np.testing.assert_array_equal(
            t.numpy(), np.broadcast_to(rows[W - 1], rows.shape)
        )

        g = tdx.all_gather(tdx.DistTensor.from_stacked(rows))
        np.testing.assert_array_equal(
            g.numpy(), np.broadcast_to(rows, (W,) + rows.shape)
        )

        # per-rank value is a (W, 5) chunk list
        chunks = np.arange(W * W * 5, dtype=np.float32).reshape(W, W, 5)
        rs = tdx.reduce_scatter(tdx.DistTensor.from_stacked(chunks))
        np.testing.assert_array_equal(rs.numpy(), chunks.sum(0))

        a2a = tdx.all_to_all(tdx.DistTensor.from_stacked(chunks))
        np.testing.assert_array_equal(
            a2a.numpy(), chunks.transpose(1, 0, 2)
        )
        return f"world={W} all_reduce broadcast all_gather reduce_scatter all_to_all"
    finally:
        tdx.destroy_process_group()


def phase_mnist_ddp(preset):
    """The source paper's path, as examples/mnist/main.py builds it:
    ConvNet + DDP.make_train_step + DistributedSampler/DataLoader."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import pytorch_distributed_example_tpu as tdx
    from pytorch_distributed_example_tpu.data import (
        DataLoader,
        DistributedSampler,
        load_mnist,
    )
    from pytorch_distributed_example_tpu.models import ConvNet

    cfg = preset["mnist"]
    tdx.init_process_group(backend="xla")
    try:
        W = tdx.get_world_size()
        B, K = cfg["batch_per_chip"], cfg["fused_steps"]
        train, test = load_mnist(None, train=True), load_mnist(None, train=False)
        model = ConvNet()
        rng = jax.random.PRNGKey(0)
        params = model.init(rng, jnp.zeros((1, 28, 28, 1)))
        ddp = tdx.DistributedDataParallel(model, params)
        opt = optax.sgd(0.01, momentum=0.5)

        def loss_fn(logits, y):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()

        def metric_fn(logits, y, w):
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            hit = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
            return jnp.stack([(ce * w).sum(), (hit * w).sum(), w.sum()])

        step = ddp.make_train_step(opt, loss_fn, has_rng=True)
        step_k = ddp.make_train_step(
            opt, loss_fn, has_rng=True, steps_per_call=K, unroll_steps=True
        )
        eval_step = ddp.make_eval_step(metric_fn)

        samplers = [
            DistributedSampler(train, num_replicas=W, rank=r)
            for r in range(W)
        ]
        loaders = [DataLoader(train, B, sampler=s) for s in samplers]

        def global_batches():
            epoch = 0
            while True:
                epoch += 1
                for s in samplers:
                    s.set_epoch(epoch)
                for micro in zip(*[iter(l) for l in loaders]):
                    xs = np.concatenate([x for x, _ in micro])
                    ys = np.concatenate([y for _, y in micro])
                    if xs.shape[0] == B * W:
                        yield xs, ys

        batches = global_batches()
        p, o = ddp.params, step.init_opt_state(ddp.params)
        t0 = time.perf_counter()
        losses = []
        for _ in range(cfg["single_steps"]):
            xs, ys = next(batches)
            rng, sub = jax.random.split(rng)
            p, o, loss = step(p, o, xs, ys, sub)
            losses.append(float(loss))
        t_single = time.perf_counter() - t0

        t0 = time.perf_counter()
        stack = [next(batches) for _ in range(K)]
        rng, sub = jax.random.split(rng)
        p, o, fused = step_k(
            p, o, np.stack([x for x, _ in stack]),
            np.stack([y for _, y in stack]), jax.random.split(sub, K),
        )
        losses += [float(l) for l in np.asarray(fused)]
        t_fused = time.perf_counter() - t0

        _check(all(map(_finite, losses)), f"non-finite loss: {losses}")
        head, tail = np.mean(losses[:3]), np.mean(losses[-3:])
        _check(tail < head, f"loss did not fall: {head:.4f} -> {tail:.4f}")

        n = B * W
        x, y = test[np.arange(n) % len(test)]
        m = np.asarray(eval_step(p, x, y, np.ones((n,), np.float32)))
        _check(_finite(m) and m[2] == n, f"eval metrics wrong: {m}")

        print(f"  smoke timing (compile included): {cfg['single_steps']} "
              f"single-dispatch steps {t_single:.1f} s, one "
              f"steps_per_call={K} call {t_fused:.1f} s")
        if W >= 4:
            _check(step.weight_update_sharded, "world>1 but update not sharded")
            _check_all_hold("mnist ddp params", p, jax.devices())
            # the ZeRO update keeps 1/W of the momentum on each device
            _check_all_hold("mnist ddp optimizer state (ZeRO)", o,
                            jax.devices(), max_fraction=0.5)
        return (f"world={W} sharded_update={step.weight_update_sharded} "
                f"loss {head:.4f} -> {tail:.4f} eval_acc={m[1] / m[2]:.3f}")
    finally:
        tdx.destroy_process_group()


def phase_lm_train(preset):
    """TransformerLM at full width on ONE chip through
    DistributedDataParallel.make_train_step (the default
    shard_weight_update="auto" path): bf16, per-block remat, AdamW, flash."""
    import jax

    import pytorch_distributed_example_tpu as tdx

    lm = preset["lm"]
    tdx.init_process_group(backend="xla", world_size=1)
    try:
        last_oom = None
        for depth in lm["depths"]:
            try:
                return _lm_train_at(lm, depth)
            except jax.errors.JaxRuntimeError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                last_oom = e
                print(f"  depth {depth} does not fit: cutting depth "
                      "(widths unchanged)")
                gc.collect()
        raise last_oom
    finally:
        tdx.destroy_process_group()


def _lm_train_at(lm, depth):
    import jax
    import numpy as np
    import optax

    import pytorch_distributed_example_tpu as tdx

    B, L = lm["batch_per_chip"], lm["seq"]
    t0 = time.perf_counter()
    model, params = _lm(lm, depth)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    ddp = tdx.DistributedDataParallel(model, params)
    opt = optax.adamw(lm["lr"])
    step = ddp.make_train_step(opt, _next_token_loss)
    toks = np.random.default_rng(0).integers(
        0, lm["vocab"], (B, L)
    ).astype(np.int32)
    p, o = ddp.params, step.init_opt_state(ddp.params)
    t_setup = time.perf_counter() - t0

    losses, times = [], []
    for _ in range(lm["steps"]):
        t0 = time.perf_counter()
        p, o, loss = step(p, o, toks, toks)  # one batch: it must memorize
        jax.block_until_ready(loss)
        t_block = time.perf_counter() - t0
        losses.append(float(loss))
        times.append(time.perf_counter() - t0)
    # does block_until_ready wait? If it returned early, the readback that
    # follows would carry the step's time instead of microseconds.
    t_readback = times[-1] - t_block
    print(f"  last step: block_until_ready returned after {t_block:.3f} s, "
          f"the readback after it took {t_readback * 1e3:.2f} ms")
    _check(t_readback < 0.2 * times[-1] + 0.01,
           "block_until_ready returned before the step finished")
    _check(all(map(_finite, losses)), f"non-finite loss: {losses}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    _assert_kernel_path(
        "lm trainer", step._jitted,
        (p, o, {}, toks, toks, jax.random.PRNGKey(0)),
    )
    stats = jax.devices()[0].memory_stats()
    peak = stats["peak_bytes_in_use"] if stats else None
    print(f"  depth used: {depth} layers; {n_params / 1e6:.0f}M params; "
          f"batch {B} x {L}")
    print(f"  set-up {t_setup:.1f} s; first step (compile) {times[0]:.1f} s; "
          f"later steps (smoke timing) "
          f"{', '.join(f'{t:.2f}' for t in times[1:])} s")
    print(f"  peak_bytes_in_use = {peak}")
    return (f"depth={depth} loss {losses[0]:.4f} -> {losses[-1]:.4f} "
            f"peak_bytes={peak}")


def phase_kernel_numerics(preset):
    """flash_attention forward and jax.grad on the chip against the f32
    dense reference, resident and streamed kernels."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.ops import flash_attention
    from pytorch_distributed_example_tpu.ops.flash_attention import (
        _use_streaming,
    )

    # Tolerance, as max |err| / max |reference| per tensor. The inputs are
    # bf16 and identical on both sides; the reference keeps f32 throughout.
    # The kernel rounds three times at bf16 precision (2^-9 relative each):
    # q*scale and the probabilities p enter the MXU as bf16 passes, and the
    # output (or gradient) is stored as bf16. Errors of that size on values
    # whose maximum is the normaliser give about 1e-2; 5e-2 leaves room for
    # accumulation over 16k keys and is still far below what a wrong mask,
    # scale or block index produces (order 1).
    TOL = 5e-2

    notes = []
    for case in preset["kernels"]:
        B, L, H, D = case["shape"]
        streamed = _use_streaming(L, D, 2)
        if case["streamed"] is not None:
            _check(streamed == case["streamed"],
                   f"L={L}: expected streamed={case['streamed']}")
        keys = jax.random.split(jax.random.PRNGKey(L), 4)
        q, k, v = (
            jax.random.normal(kk, (B, L, H, D), jnp.bfloat16)
            for kk in keys[:3]
        )
        w = jax.random.normal(keys[3], (B, L, H, D), jnp.float32)

        def loss(q, k, v):
            o = flash_attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32) * w), o

        t0 = time.perf_counter()
        (_, o), grads = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        )(q, k, v)
        jax.block_until_ready(grads)
        t_kernel = time.perf_counter() - t0
        want = _reference_attention(q, k, v, w)
        errs = {
            name: _rel_err(got, ref)
            for name, got, ref in zip(
                ("o", "dq", "dk", "dv"), (o,) + tuple(grads), want
            )
        }
        kind = "streamed" if streamed else "resident"
        print(f"  {case['shape']} bf16 causal, {kind} kernels: "
              + " ".join(f"{n}={e:.2e}" for n, e in errs.items())
              + f" (tol {TOL:.0e}; compile+run {t_kernel:.1f} s)")
        _check(all(_finite(g) for g in (o,) + tuple(grads)), "non-finite")
        _check(max(errs.values()) <= TOL, f"{case['shape']}: {errs}")
        notes.append(f"L={L}:{kind}:max_rel_err={max(errs.values()):.1e}")
    return " ".join(notes)


class _PrefillProbe:
    """Wraps the engine's prefill program to keep the last chunk's
    (start, logits): the first-token logits of the request that just
    finished prefilling."""

    def __init__(self, program):
        self.program = program
        self.last = None

    def __call__(self, params, tree, chunk, bt_row, start):
        tree, logits = self.program(params, tree, chunk, bt_row, start)
        self.last = (int(start), logits)
        return tree, logits


def _serve_engine(model, params, sv, **kw):
    from pytorch_distributed_example_tpu.serve import ServeEngine

    return ServeEngine(
        model, params, slots=sv["slots"], block_size=sv["block_size"],
        pool_blocks=sv["pool_blocks"], prefill_chunk_tokens=sv["chunk"],
        **kw,
    )


def _check_completions(done, want):
    for rid, n_new in want.items():
        _check(rid in done, f"request {rid} never completed")
        c = done[rid]
        _check(len(c.tokens) == n_new and c.finish_reason == "length",
               f"{rid}: {len(c.tokens)} tokens ({c.finish_reason}), "
               f"wanted {n_new}")


def _serve_traffic(engine, sv, vocab, lens, new_tokens, shared_prefix):
    """Submit one request per entry of ``lens`` (two more sharing a
    prefix when asked), run to completion, check token counts."""
    import numpy as np

    gen = np.random.default_rng(1)
    prompt = lambda n: gen.integers(0, vocab, (n,)).astype(np.int32)
    want = {}
    if shared_prefix:
        head = prompt(sv["prefix_len"])
        first = np.concatenate([head, prompt(sv["prefix_tail"])])
        indexed = engine.prefix.stats()["inserts"]
        rid = engine.submit(first, new_tokens[0], rid="shared-a")
        want[rid] = new_tokens[0]
        # the sibling must arrive after the first prompt is indexed, or
        # there is nothing to hit
        while engine.prefix.stats()["inserts"] == indexed:
            _check(engine.step(), "engine drained before indexing a prompt")
        second = np.concatenate([head, prompt(sv["prefix_tail"])])
        rid = engine.submit(second, new_tokens[1], rid="shared-b")
        want[rid] = new_tokens[1]
    for i, (n, new) in enumerate(zip(lens, new_tokens)):
        rid = engine.submit(prompt(n), new, rid=f"r{i}-len{n}")
        want[rid] = new
    done = engine.run(max_steps=20000)
    _check_completions(done, want)
    return len(want)


def _latent_serve(lm, sv, streams=False):
    """A toy latent-attention model (two layers, sandwich norms, a dense
    and a sigmoid-routed sparse MLP) through ServeEngine: a prompt that ends
    inside a bucket prefilled in chunks over the latent paged cache, then
    decoded; the first token's logits and every decoded token against the
    cache-free forward, which up-projects keys and values where the cached
    paths run absorbed. A broken latent kernel shows here, before a 13 GB
    model is built around it.

    With `streams` the blocks carry FOUR residual streams (hyper-connection
    maps around each sublayer), the router chooses with a bias, the rope is
    YaRN's with its factor on the softmax scale, and the engine shares
    prefixes: a request before the checked one prefills and indexes the
    prompt's head (it ends inside a block), the checked prompt attaches it,
    copies that block at its first write and prefills its own tail. A broken
    map or attach shows here, before an 11 GB model is built."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_example_tpu.models.transformer import (
        LayerSpec, RopeSpec, TransformerConfig, TransformerLM,
    )

    la = sv["latent"]
    rope, extra = None, dict(sandwich_norm=True)
    if streams:
        rope = RopeSpec(10000.0, yarn=(8.0, 64, 32.0, 1.0, 1.0), softmax_factor=1.46)
        extra = dict(hc_mult=4, sparse_choice_bias=True)
    cfg = TransformerConfig(
        vocab_size=lm["vocab"], d_model=la["d_model"], n_layers=2,
        n_heads=la["n_heads"], d_ff=2 * la["d_model"], max_seq_len=sv["max_seq_len"],
        dtype=jnp.bfloat16, use_flash=False, **extra,
        layers=(LayerSpec("latent", rope=rope), LayerSpec("latent", rope=rope, mlp="sparse")),
        latent_q_rank=la["q_rank"], latent_kv_rank=la["kv_rank"],
        latent_nope_dim=la["nope"], latent_rope_dim=la["rope"], latent_v_dim=la["v"],
        sparse_score="sigmoid", sparse_experts=8, sparse_top_k=2,
        sparse_d_ff=la["d_model"], shared_d_ff=la["d_model"], routed_scale=2.5,
    )
    model = TransformerLM(cfg)
    params = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16),
        model.init(key, jnp.zeros((1, 8), jnp.int32))))(jax.random.PRNGKey(3))["params"]
    engine = _serve_engine(model, params, sv, prefix_cache=streams)
    probe = engine._prefill_chunk = _PrefillProbe(engine._prefill_chunk)
    paths = (engine.metrics.decode_layer_paths["latent"][1],
             engine.metrics.prefill_layer_paths["latent"][1])
    if jax.default_backend() == "tpu":
        _check(paths == ("latent_decode_kernel", "latent_chunk_kernel"),
               f"the latent layers take {paths} on a TPU, not their kernels")
    toks = np.random.default_rng(5).integers(
        0, lm["vocab"], (la["prompt_len"],)).astype(np.int32)
    n_new = la["new_tokens"]
    head = 2 * len(toks) // 3 // sv["block_size"] * sv["block_size"] + sv["block_size"] // 2
    if streams:
        holder = np.concatenate([toks[:head], (toks[head:head + 9] + 1) % lm["vocab"]])
        engine.submit(holder, 2, rid="holder")
        engine.run(max_steps=2000)
    rid = engine.submit(toks, n_new, rid="latent")
    done = engine.run(max_steps=2000)
    _check_completions(done, {rid: n_new})
    if streams:
        reused = engine.prefix.stats()["prefix_tokens_reused"]
        _check(reused == head and engine.cache.cow_copies >= 1,
               f"the prompt attached {reused} of its head's {head} tokens "
               f"({engine.cache.cow_copies} blocks copied on write)")
    seq = np.concatenate([toks, np.asarray(done[rid].tokens[:-1], np.int32)])
    want = np.asarray(jax.jit(lambda p, t: model.apply({"params": p}, t))(
        params, jnp.asarray(seq)[None])[0, -n_new:], np.float32)
    start, chunk_logits = probe.last
    err = _rel_err(chunk_logits[(len(toks) - 1) - start], want[0])
    picked = want[np.arange(n_new), done[rid].tokens]
    gap = float(((want.max(axis=1) - picked) / np.abs(want).max()).max())
    what = f"four streams, {head} tokens attached; " if streams else ""
    print(f"  latent layers ({what}{paths[0]}, {paths[1]}): first-token logits rel err "
          f"{err:.2e}, decoded tokens within {gap:.2e} of the forward's best logit")
    _check(err <= 1e-1 and gap <= 1e-1,
           f"the latent cached paths disagree with the forward: {err}, {gap}")
    _check(engine.cache.live_blocks == 0, "latent blocks were not freed at retirement")
    return err


def phase_serve(preset):
    """ServeEngine on the full-width bf16 model: paged cache, chunked
    prefill, prefix sharing; first-token logits against a plain
    full-sequence model.apply; a toy latent-attention model through the
    same engine (`_latent_serve`), and one with four residual streams whose
    prompt attaches a shared head; then int8 KV for completion."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    lm, sv = preset["lm"], preset["serve"]
    depth = lm["depths"][0]
    t0 = time.perf_counter()
    model, variables = _lm(dict(lm, seq=sv["max_seq_len"]), depth)
    params = variables["params"]
    engine = _serve_engine(model, params, sv, prefix_cache=True)
    probe = engine._prefill_chunk = _PrefillProbe(engine._prefill_chunk)
    t_setup = time.perf_counter() - t0

    # Tolerance for first-token logits, as max |err| / max |reference|:
    # both sides are the same bf16 weights and bf16 activations through
    # `depth` layers, but the engine attends over the paged cache with a
    # dense einsum in chunks while model.apply runs the flash kernel over
    # the whole prompt — different bf16 rounding points in every layer.
    # Per-layer bf16 noise (2^-9) compounds slowly: the CPU rehearsal at
    # d_model 256 reads 1e-2 of the logit range at 2 layers and 2e-2 at
    # 16. The limit is 1e-1; a cache-indexing or RoPE-offset bug shows as
    # order 1.
    TOL = 1e-1
    full = jax.jit(lambda p, t: model.apply({"params": p}, t))
    errs = []
    t0 = time.perf_counter()
    for seed in (11, 12):
        toks = np.random.default_rng(seed).integers(
            0, lm["vocab"], (sv["check_len"],)
        ).astype(np.int32)
        rid = engine.submit(toks, 2, rid=f"check{seed}")
        _check_completions(engine.run(max_steps=2000), {rid: 2})
        start, chunk_logits = probe.last
        got = chunk_logits[(len(toks) - 1) - start]
        want = full(params, jnp.asarray(toks)[None])[0, -1]
        errs.append(_rel_err(got, want))
    t_check = time.perf_counter() - t0
    print(f"  first-token logits vs full-sequence apply (len "
          f"{sv['check_len']}): rel err {errs[0]:.2e}, {errs[1]:.2e} "
          f"(tol {TOL:.0e})")
    _check(max(errs) <= TOL, f"first-token logits disagree: {errs}")

    t0 = time.perf_counter()
    n = _serve_traffic(engine, sv, lm["vocab"], sv["prompt_lens"],
                       sv["new_tokens"], shared_prefix=True)
    t_traffic = time.perf_counter() - t0
    reused = engine.prefix.stats()["prefix_tokens_reused"]
    floor = sv["prefix_len"] - sv["block_size"]
    _check(reused >= floor, f"prefix cache reused {reused} tokens < {floor}")
    print(f"  plain KV: {n} requests completed, prefix tokens reused "
          f"{reused}; set-up {t_setup:.1f} s, logits check {t_check:.1f} s, "
          f"traffic {t_traffic:.1f} s (smoke timings, compile included)")
    _check_all_hold("serve", (engine.params, engine.cache.tree),
                    jax.devices()[:1])
    del engine, probe
    gc.collect()
    latent_err = max(_latent_serve(lm, sv), _latent_serve(lm, sv, streams=True))

    t0 = time.perf_counter()
    engine = _serve_engine(model, params, sv, kv_quant=True)
    nq = _serve_traffic(
        engine, sv, lm["vocab"], sv["quant_prompt_lens"],
        [sv["quant_new_tokens"]] * len(sv["quant_prompt_lens"]),
        shared_prefix=False,
    )
    print(f"  int8 KV: {nq} requests completed in "
          f"{time.perf_counter() - t0:.1f} s (smoke timing)")
    return (f"depth={depth} plain={n + 2} int8={nq} "
            f"logits_rel_err={max(errs):.1e} prefix_reused={reused} "
            f"latent_rel_err={latent_err:.1e}")


def phase_four_chip(preset):
    """What needs more than one chip: the LM trainer under fully_shard on
    ("fsdp","tp") = (2,2) and (4,1) with flash on, tp=2 serve, ring
    attention, EP MoE, pipeline — each with all four devices holding bytes.
    (Collectives and MNIST DDP run at world 4 in their own phases.)"""
    import jax

    n = len(jax.devices())
    if n < 4:
        raise Skip(f"devices={n}")
    devs = jax.devices()[:4]
    notes = []
    for shape in preset["four"]["lm_meshes"]:
        notes.append(_four_lm(preset, devs, shape))
        gc.collect()
    notes.append(_four_serve_tp(preset, devs))
    gc.collect()
    notes.append(_four_ring(preset, devs))
    notes.append(_four_moe(preset, devs))
    notes.append(_four_pipeline(preset, devs))
    _replica_placement_note(preset, devs)
    return " | ".join(notes)


def _four_lm(preset, devs, shape):
    import jax
    import numpy as np
    import optax

    from pytorch_distributed_example_tpu.mesh import init_device_mesh
    from pytorch_distributed_example_tpu.models import (
        transformer_sharding_rules,
    )
    from pytorch_distributed_example_tpu.parallel import fully_shard

    lm, B = preset["lm"], preset["four"]["lm_batch"]
    depth = lm["depths"][0]
    mesh = init_device_mesh(("fsdp", "tp"), shape, devices=devs)
    model, params = _lm(lm, depth)
    mod = fully_shard(
        model, params, mesh, axis="fsdp",
        rules=transformer_sharding_rules("tp", "fsdp"), data_axes=("fsdp",),
    )
    del params
    opt = optax.adamw(lm["lr"])
    step = mod.make_train_step(opt, _next_token_loss)
    toks = np.random.default_rng(0).integers(
        0, lm["vocab"], (B, lm["seq"])
    ).astype(np.int32)
    p, o = mod.params, step.init_opt_state(mod.params)
    _assert_kernel_path(f"fully_shard {shape}", step, (p, o, toks, toks))
    losses = []
    t0 = time.perf_counter()
    for _ in range(lm["steps"]):
        p, o, loss = step(p, o, toks, toks)
        losses.append(float(loss))
    t = time.perf_counter() - t0
    _check(all(map(_finite, losses)), f"{shape}: non-finite loss {losses}")
    _check(losses[-1] < losses[0], f"{shape}: loss did not fall: {losses}")
    # four-way sharded matrices, replicated norms (and, at tp=1, the
    # embedding): a quarter each and some — never half
    _check_all_hold(f"fully_shard fsdp x tp = {shape}", (p, o), devs,
                    max_fraction=0.5)
    print(f"  fully_shard {shape}: depth {depth}, batch {B} x {lm['seq']}, "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}, {lm['steps']} steps "
          f"{t:.1f} s (smoke timing, compile included)")
    return f"fsdp{shape[0]}xtp{shape[1]}:loss {losses[0]:.3f}->{losses[-1]:.3f}"


def _four_serve_tp(preset, devs):
    from pytorch_distributed_example_tpu.mesh import init_device_mesh

    lm, sv = preset["lm"], preset["serve"]
    model, variables = _lm(dict(lm, seq=sv["max_seq_len"]), lm["depths"][0])
    mesh = init_device_mesh(("tp",), (2,), devices=devs[:2])
    engine = _serve_engine(model, variables["params"], sv, mesh=mesh)
    n = _serve_traffic(
        engine, sv, lm["vocab"], sv["quant_prompt_lens"],
        [sv["quant_new_tokens"]] * len(sv["quant_prompt_lens"]),
        shared_prefix=False,
    )
    _check_all_hold("serve tp=2", (engine.params, engine.cache.tree), devs[:2])
    return f"serve_tp2:{n} requests"


def _four_ring(preset, devs):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.mesh import init_device_mesh
    from pytorch_distributed_example_tpu.parallel import make_cp_attention

    B, L, H, D = preset["four"]["ring"]
    mesh = init_device_mesh(("sp",), (4,), devices=devs)
    attn = make_cp_attention(mesh, axis_name="sp", mode="ring", causal=True)
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (
        jax.random.normal(kk, (B, L, H, D), jnp.bfloat16) for kk in keys
    )
    if preset is PRESETS["chip"]:
        _assert_kernel_path("ring attention", attn, (q, k, v))
    o = attn(q, k, v)
    want = _reference_attention(q, k, v, jnp.zeros(q.shape, jnp.float32))[0]
    err = _rel_err(o, want)
    # same reasoning and limit as phase_kernel_numerics: bf16 in, f32 ring
    # combine, bf16 out
    _check(_finite(o) and err <= 5e-2, f"ring attention rel err {err}")
    _check_all_hold("ring attention", o, devs)
    return f"ring L={L}:rel_err={err:.1e}"


def _four_moe(preset, devs):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.mesh import init_device_mesh
    from pytorch_distributed_example_tpu.parallel import make_ep_moe
    from pytorch_distributed_example_tpu.parallel.expert_parallel import (
        moe_mlp,
    )

    c = preset["four"]["moe"]
    T, D, F, E = c["tokens"], c["d"], c["f"], c["experts"]
    mesh = init_device_mesh(("ep",), (4,), devices=devs)
    # capacity = every token: nothing drops, so the sharded dispatch must
    # agree with the all-local form token for token
    moe = make_ep_moe(mesh, "ep", capacity_factor=float(E))
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(keys[0], (T, D), jnp.float32)
    wu = jax.random.normal(keys[1], (E, D, F), jnp.float32) * D ** -0.5
    wd = jax.random.normal(keys[2], (E, F, D), jnp.float32) * F ** -0.5
    rw = jax.random.normal(keys[3], (D, E), jnp.float32) * D ** -0.5
    y, aux = moe(x, wu, wd, rw)
    want, _ = jax.jit(
        lambda *a: moe_mlp(*a, axis_name=None, capacity_factor=float(E))
    )(x, wu, wd, rw)
    err = _rel_err(y, want)
    # both sides run the same default-precision (bf16-pass) matmuls on the
    # same values; only the batching of tokens per expert differs
    _check(_finite(y) and _finite(aux) and err <= 2e-2, f"ep moe rel err {err}")
    _check_all_hold("ep moe", y, devs)
    return f"ep_moe d={D}:rel_err={err:.1e}"


def _four_pipeline(preset, devs):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.mesh import init_device_mesh
    from pytorch_distributed_example_tpu.parallel import (
        make_pipeline_fn,
        stack_stage_params,
    )

    c = preset["four"]["pipe"]
    d, M, mb = c["d"], c["micro"], c["mb"]
    mesh = init_device_mesh(("pp",), (4,), devices=devs)
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    ws = [jax.random.normal(kk, (d, d), jnp.float32) * d ** -0.5
          for kk in keys[:4]]
    x = jax.random.normal(keys[4], (M, mb, d), jnp.float32)
    stage = lambda p, h: jnp.tanh(h @ p["w"])
    pipe = make_pipeline_fn(stage, mesh, "pp")
    # stage i's weights on device i, as make_pipeline_fn's contract asks
    stacked = jax.device_put(
        stack_stage_params([{"w": w} for w in ws]),
        jax.sharding.NamedSharding(
            mesh.jax_mesh, jax.sharding.PartitionSpec("pp")
        ),
    )
    y = pipe(stacked, x)
    want = x
    for w in ws:
        want = stage({"w": w}, want)
    err = _rel_err(y, want)
    # agreeing with the four stages applied in order is what shows every
    # stage ran with its own weights; the output comes back replicated, so
    # each device must hold a copy of it
    _check(_finite(y) and err <= 2e-2, f"pipeline rel err {err}")
    _check_all_hold("pipeline output", y, devs)
    return f"pipeline d={d}:rel_err={err:.1e}"


def _replica_placement_note(preset, devs):
    """Not a check — a finding for the benchmark PR: where do in-process
    serve replicas land when nothing places them?"""
    import jax

    from pytorch_distributed_example_tpu.mesh import init_device_mesh

    tiny = PRESETS["tiny"]
    model, variables = _lm(
        dict(tiny["lm"], seq=tiny["serve"]["max_seq_len"]), 2
    )
    unplaced = [
        _serve_engine(model, variables["params"], tiny["serve"])
        for _ in range(2)
    ]
    placed = _serve_engine(
        model, variables["params"], tiny["serve"],
        mesh=init_device_mesh(("tp",), (1,), devices=devs[3:4]),
    )
    where = lambda e: sorted({
        d.id
        for leaf in jax.tree_util.tree_leaves(e.cache.tree)
        for d in leaf.devices()
    })
    print(f"  replica placement: two ServeEngine() without mesh= put their "
          f"pools on devices {[where(e) for e in unplaced]}; with a "
          f"one-device mesh= the pool is on {where(placed)}")


PHASES = (
    ("collectives", phase_collectives),
    ("mnist_ddp", phase_mnist_ddp),
    ("lm_train", phase_lm_train),
    ("kernel_numerics", phase_kernel_numerics),
    ("serve", phase_serve),
    ("four_chip", phase_four_chip),
)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def _require_tpu():
    """The device as JAX reports it, or exit nonzero naming what was found."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: no accelerator — jax.devices() raised: {e}")
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU — jax.devices()[0].platform is "
            f"{d.platform!r} ({d.device_kind}, {len(devs)} device(s)). "
            "This script runs on the chip only."
        )
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def run_phases(preset, names, require_four_chips=False) -> dict:
    """Run the named phases in PHASES order, print one line each, and
    return {name: verdict}: "PASS", "SKIP(devices=N)" or "FAIL...". """
    verdicts = {}
    for name, fn in PHASES:
        if name not in names:
            continue
        print(f"--- {name}", flush=True)
        t0 = time.perf_counter()
        try:
            note = fn(preset)
            verdict = "PASS"
        except Skip as s:
            note = ""
            verdict = f"SKIP({s})"
            if require_four_chips:
                verdict = f"FAIL (required, but {s})"
        except Exception:
            # the boundary that must keep going: report, count, move on —
            # the exit code carries the failure
            traceback.print_exc(file=sys.stdout)
            note, verdict = "", "FAIL"
        gc.collect()
        print(f"{name}: {verdict} [{time.perf_counter() - t0:.1f} s] {note}",
              flush=True)
        verdicts[name] = verdict
    return verdicts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--require-four-chips", action="store_true",
                    help="fail, not skip, when fewer than 4 devices are visible")
    ap.add_argument("--only", default=",".join(n for n, _ in PHASES),
                    help="comma-separated subset of phases to run; a subset "
                         "run prints no result line")
    args = ap.parse_args(argv)
    names = args.only.split(",")
    unknown = set(names) - {n for n, _ in PHASES}
    if unknown:
        ap.error(f"unknown phase(s) {sorted(unknown)}")

    t_start = time.perf_counter()
    device = _require_tpu()

    import jax

    from pytorch_distributed_example_tpu import _native
    from pytorch_distributed_example_tpu._compat import enable_compile_cache

    cache_dir = enable_compile_cache()
    counters = CacheCounters()
    print(f"jax {jax.__version__}; platform {device['platform']}; "
          f"device_kind {device['kind']}; devices {device['count']}")
    print(f"compile cache: {cache_dir} "
          f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'in-checkout default'})")
    print(f"native library: {_native.status()}")

    # the chip preset and no other: the result line stands for full width
    verdicts = run_phases(PRESETS["chip"], names, args.require_four_chips)

    counters.close()
    c = counters.counts
    print(f"persistent compile cache: {c['requests']} requests, "
          f"{c['hits']} hits, {c['written']} entries written")
    print(f"total wall time {time.perf_counter() - t_start:.1f} s "
          "(set-up and smoke timing, compile included)")
    print("phases: " + "; ".join(f"{n} {v}" for n, v in verdicts.items()))
    if any(v.startswith("FAIL") for v in verdicts.values()):
        print("chip_smoke: FAILED")
        return 1
    not_run = [n for n, _ in PHASES if n not in verdicts]
    if not_run:
        print(f"chip_smoke: the phases run passed, but {', '.join(not_run)} "
              "did not run (--only): no result line, which stands for every "
              "phase")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
