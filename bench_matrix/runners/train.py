"""Runs a training cell: the trainer named by the traffic file steps over
seeded, device-resident batches, back to back with no per-step sync; a
sub-window is a fixed number of steps that ends in `block_until_ready`.
"""

from __future__ import annotations

import statistics

import jax
import numpy as np

from .. import correctness, modelglue, spec, traffic_gen
from ..context import Context, Result


def _check_logits(cell, ctx, trainer, tokens_len, last):
    """The trainer's own forward pass against the float32 reference."""
    config, limits = cell["config"], cell["correctness"]
    seq = traffic_gen.check_sequence(config["vocab_size"], ctx.seed, tokens_len)
    x = trainer.place(np.tile(seq[None], (trainer.rows, 1)))
    got = np.asarray(trainer.forward(trainer.params, x)[0, -last:])
    want = correctness.reference_logits(
        config, trainer.params, seq, last, device=trainer.device
    )
    out = correctness.compare(got, want, limits)
    ctx.say(
        f"correctness: trainer forward vs float32 reference, {tokens_len} "
        f"tokens, last {last}: max_rel {out['max_rel']:.3e} (limit "
        f"{limits['max_rel']}), rms_rel {out['rms_rel']:.3e} (limit "
        f"{limits['rms_rel']})"
    )
    return out["ok"]


def run(cell: dict, ctx: Context) -> Result:
    config, traffic = cell["config"], cell["traffic"]
    model = modelglue.build_model(config, traffic["seq"], traffic["remat"])
    trainer = spec.module("trainers", traffic["trainer"]).Trainer(
        model, config, traffic, ctx.seed, ctx.devices
    )
    try:
        return _run(cell, ctx, trainer)
    finally:
        trainer.close()


def _run(cell, ctx, trainer) -> Result:
    config, traffic = cell["config"], cell["traffic"]
    clock = ctx.clock
    batches = [
        trainer.place(b)
        for b in traffic_gen.train_batches(traffic, config["vocab_size"], ctx.seed)
    ]
    ctx.say(f"weights and batches on the device at {clock() - ctx.t_start:.1f} s")
    correct = _check_logits(
        cell, ctx, trainer, traffic["seq"], cell["correctness"]["last_positions"]
    )
    ctx.say(f"reference check done at {clock() - ctx.t_start:.1f} s")

    step = trainer.step
    params, opt_state = trainer.params, trainer.opt_state
    trainer.params = trainer.opt_state = None  # donated from the first step on
    n_sub = traffic["steps_per_subwindow"]
    n_steps = 0
    dispatch_s, losses = [], []

    def subwindow(steps):
        """`steps` dispatches and one wait; seconds per step."""
        nonlocal params, opt_state, n_steps
        t0 = clock()
        for _ in range(steps):
            x = batches[n_steps % len(batches)]
            with ctx.span("step dispatch"):
                t = clock()
                params, opt_state, loss = step(params, opt_state, x, x)
                dispatch_s.append(clock() - t)
            losses.append(loss)
            n_steps += 1
        with ctx.span("readback"):
            jax.block_until_ready(loss)
        return (clock() - t0) / steps

    # warm-up: the first sub-window compiles or loads the program; then
    # until two consecutive sub-windows agree (the ramp of ROADMAP S2)
    warm = [subwindow(n_sub)]
    ctx.say(f"first sub-window (compile or cache load) {warm[0] * n_sub:.1f} s")
    while len(warm) < traffic["warmup_max_subwindows"] + 1:
        warm.append(subwindow(n_sub))
        a, b = warm[-2], warm[-1]
        if len(warm) > 2 and abs(a - b) <= traffic["warmup_agree"] * min(a, b):
            break
    ctx.say(
        "warm-up sub-windows, ms per step: "
        + ", ".join(f"{1e3 * w:.1f}" for w in warm)
    )
    first_loss = float(np.mean([float(l) for l in losses[:n_sub]]))

    del dispatch_s[:], losses[:]
    requests_before = ctx.compiles.requests
    t_open = clock()
    setup_s = t_open - ctx.t_start
    subs = []
    while clock() - t_open < ctx.seconds:
        subs.append(subwindow(n_sub))
    window_s = clock() - t_open
    compiles = ctx.compiles.requests - requests_before
    window_losses = [float(l) for l in losses]
    window_dispatch = list(dispatch_s)

    if ctx.trace_dir:
        with ctx.tracing():
            subwindow(traffic["trace_steps"])

    step_s = statistics.median(subs)
    tokens_per_step = traffic["global_batch"] * traffic["seq"]
    last_loss = float(np.mean(window_losses[-n_sub:]))
    finite = [bool(np.isfinite(l)) for l in window_losses]
    ctx.say(
        f"window {window_s:.2f} s, {len(subs)} sub-windows of {n_sub} steps, "
        "ms per step: " + ", ".join(f"{1e3 * s:.2f}" for s in subs)
    )
    ctx.say(
        f"tokens/s {tokens_per_step / step_s:.1f} ({tokens_per_step} tokens a "
        f"step); loss {first_loss:.4f} (first warm-up sub-window) -> "
        f"{last_loss:.4f} (last sub-window)"
    )
    if not all(finite):
        ctx.say("a loss was not finite")
    if not last_loss < first_loss:
        ctx.say("the loss did not fall")
    return Result(
        correct=correct and all(finite) and last_loss < first_loss,
        attempted=len(window_losses),
        failed=finite.count(False),
        metrics={"train_step_ms": 1e3 * step_s, "setup_s": setup_s},
        samples={
            "compiles_in_window": compiles,
            "dispatch_s": window_dispatch,
            "step_s": step_s,
            "tokens_per_step": tokens_per_step,
            "seq": traffic["seq"],
            "global_batch": traffic["global_batch"],
            "trace_steps": traffic["trace_steps"],
            "remat": traffic["remat"],
        },
    )
