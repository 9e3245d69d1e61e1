"""Pipeline parallelism — stage-sliced shard_map + collective-permute.

Parity surface: `torch/distributed/pipelining/` (SURVEY.md §2.3 row PP).
TPU-native design (scaling-book recipe): the ``pp`` mesh axis holds one
pipeline stage per device group; stage parameters are stacked on a leading
stage dim sharded over ``pp``; a GPipe schedule runs M microbatches through
S stages in M+S-1 ticks, shifting activations one hop along the ICI ring
with `lax.ppermute` each tick. The whole schedule is ONE compiled program —
bubbles and comm overlap are visible to (and optimized by) XLA, and
`jax.grad` differentiates straight through it (ppermute's transpose is the
reverse permute), so there is no hand-written backward schedule à la
torch pipelining's `ScheduleGPipe` runtime.

Schedules (parity: `torch/distributed/pipelining/schedules.py`):
  * **GPipe** (`ScheduleGPipe`): forward-only tick loop below; `jax.grad`
    differentiates through it, XLA schedules the backward. Activation
    memory is O(M) per stage (all microbatch residuals live until the
    backward), like GPipe everywhere.
  * **1F1B** (`Schedule1F1B`): `pipeline_train_1f1b` — explicit
    forward/backward interleaving in ONE compiled tick loop. Forward of
    microbatch m at stage i fires at tick m+i; its backward at tick
    m+2(S-1)-i; cotangents ride a reverse ppermute. Stage inputs are kept
    in a mod-(2S-1) ring and the backward recomputes the stage under
    `jax.vjp`, so activation memory is O(S) — independent of M — which is
    the whole point of 1F1B.
  * **Interleaved / looped** (`ScheduleInterleaved1F1B`-shaped):
    `virtual_stages=V` assigns stage s to device s mod S (torch's
    interleaved placement); each device applies its V stage chunks per
    tick (vmap over the chunk dim) and activations wrap around the ring V
    times, shrinking the bubble from (S-1)/(M+S-1) toward its 1/V multiple.

API:
  * `pipeline_apply(stage_fn, stage_params, x, axis_name, ...)` — inside
    shard_map: push microbatches through the ring.
  * `make_pipeline_fn(...)` — jit-ready wrapper: takes global inputs,
    shards params over ``pp``, returns global outputs.
  * `pipeline_train_1f1b(...)` / `make_pipeline_train_fn(...)` — loss +
    stacked param grads under the chosen schedule.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

from jax.lax import axis_size as _axis_size


def pipeline_apply(stage_fn: Callable, stage_params, x, axis_name: str = "pp"):
    """GPipe forward inside shard_map.

    stage_fn(params_for_stage, activation) -> activation (same shape).
    stage_params: this stage's param pytree (leading stage dim already
    consumed by shard_map's in_spec).
    x: (M, mb, ...) microbatched input, replicated across stages (only
    stage 0 reads it). Returns (M, mb, ...) final-stage outputs,
    replicated via psum so every stage exits with the result.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    S = _axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    M = x.shape[0]
    mb_shape = x.shape[1:]
    T = M + S - 1  # total ticks

    shift_perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(t, carry):
        state, out = carry
        # stage 0 ingests microbatch t (dummy past the end); others use the
        # activation shifted in from the previous stage
        mb_idx = jnp.minimum(t, M - 1)
        fresh = lax.dynamic_index_in_dim(x, mb_idx, axis=0, keepdims=False)
        inp = jnp.where(stage == 0, fresh, state)
        y = stage_fn(stage_params, inp)
        # last stage banks its result at output slot t - (S - 1)
        out_idx = jnp.clip(t - (S - 1), 0, M - 1)
        valid = jnp.logical_and(stage == S - 1, t >= S - 1)
        cur = lax.dynamic_index_in_dim(out, out_idx, axis=0, keepdims=False)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(valid, y, cur), out_idx, axis=0
        )
        # shift activations one hop along the ring for the next tick
        state = lax.ppermute(y, axis_name, shift_perm)
        return state, out

    state0 = jnp.zeros(mb_shape, x.dtype)
    out0 = jnp.zeros((M,) + mb_shape, x.dtype)
    _, out = lax.fori_loop(0, T, tick, (state0, out0))
    # replicate the last stage's banked outputs to every stage
    mask = (stage == S - 1).astype(out.dtype)
    return lax.psum(out * mask, axis_name)


def pipeline_apply_interleaved(
    stage_fn: Callable, chunk_params, x, axis_name: str = "pp"
):
    """Interleaved (looped) forward inside shard_map.

    Global stage s (of V*S) lives on device s mod S, chunk v = s // S —
    torch's `ScheduleInterleaved1F1B` placement. `chunk_params` carries this
    device's V chunks stacked on the leading dim; activations travel the
    ring V times, and each device advances all V chunks per tick (vmap), so
    the warm-up/drain bubble per unit of work shrinks by ~1/V vs GPipe.
    Differentiable; `jax.grad` yields the interleaved backward.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    S = _axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    V = jax.tree_util.tree_leaves(chunk_params)[0].shape[0]
    M = x.shape[0]
    mb_shape = x.shape[1:]
    T = M + V * S - 1  # mb m finishes global stage VS-1 at tick m + VS - 1

    shift_perm = [(i, (i + 1) % S) for i in range(S)]
    is_first = stage == 0
    is_last = stage == S - 1

    def tick(t, carry):
        state, out = carry  # state: (V, *mb) shifted-in activations
        mb_idx = jnp.clip(t, 0, M - 1)
        fresh = lax.dynamic_index_in_dim(x, mb_idx, axis=0, keepdims=False)
        # chunk v input: device 0 wraps chunk v-1 (or ingests x at v=0);
        # other devices take the shifted-in chunk-v activation
        wrapped = jnp.concatenate([fresh[None], state[:-1]], axis=0)
        inp = jnp.where(is_first, wrapped, state)
        y = jax.vmap(stage_fn)(chunk_params, inp)
        # bank the last chunk's output on the last device
        out_idx = jnp.clip(t - (V * S - 1), 0, M - 1)
        valid = jnp.logical_and(is_last, t >= V * S - 1)
        cur = lax.dynamic_index_in_dim(out, out_idx, axis=0, keepdims=False)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(valid, y[V - 1], cur), out_idx, axis=0
        )
        state = lax.ppermute(y, axis_name, shift_perm)
        return state, out

    state0 = jnp.zeros((V,) + mb_shape, x.dtype)
    out0 = jnp.zeros((M,) + mb_shape, x.dtype)
    _, out = lax.fori_loop(0, T, tick, (state0, out0))
    mask = (stage == S - 1).astype(out.dtype)
    return lax.psum(out * mask, axis_name)


def pipeline_train_1f1b(
    stage_fn: Callable,
    loss_fn: Callable,
    stage_params,
    x,
    targets,
    axis_name: str = "pp",
):
    """1F1B train schedule inside shard_map: returns (mean loss, param grads).

    stage_fn(params, activation) -> activation (same shape across stages).
    loss_fn(final_activation, target_microbatch) -> scalar (per-microbatch
    mean); the returned loss and grads are averaged over microbatches so
    they match `loss_fn` applied to the full batch.

    Tick t on stage i (all SPMD, masked):
      fwd microbatch m_f = t - i           (consumes fwd ppermute shift-in)
      bwd microbatch m_b = t - 2(S-1) + i  (consumes bwd ppermute shift-in;
                                            the LAST stage seeds from its
                                            own same-tick loss gradient)
    Stage inputs are banked in a ring of depth 2S-1 (max concurrently
    in-flight microbatches at stage 0) and the backward recomputes the
    stage under `jax.vjp` — recompute-over-store, the TPU-idiomatic trade.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    S = _axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    M = x.shape[0]
    mb_shape = x.shape[1:]
    D = 2 * S - 1  # residual ring depth = max in-flight at stage 0
    T = M + 2 * S - 2  # ticks until the last backward (m=M-1, i=0) fires

    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]
    is_last = stage == S - 1
    is_first = stage == 0

    zeros_like_params = jax.tree_util.tree_map(jnp.zeros_like, stage_params)

    def tick(t, carry):
        fwd_state, bwd_state, resid, grad_acc, loss_acc = carry

        # ---- forward half: microbatch m_f through this stage ------------
        m_f = t - stage
        fwd_valid = jnp.logical_and(m_f >= 0, m_f < M)
        m_f_c = jnp.clip(m_f, 0, M - 1)
        fresh = lax.dynamic_index_in_dim(x, m_f_c, axis=0, keepdims=False)
        inp = jnp.where(is_first, fresh, fwd_state)
        # bank the stage input for the (recomputed) backward
        slot_f = m_f_c % D
        old = lax.dynamic_index_in_dim(resid, slot_f, axis=0, keepdims=False)
        resid = lax.dynamic_update_index_in_dim(
            resid, jnp.where(fwd_valid, inp, old), slot_f, axis=0
        )
        y = stage_fn(stage_params, inp)

        # loss + seed cotangent for the LAST stage (same-tick: m_b == m_f)
        tgt = lax.dynamic_index_in_dim(targets, m_f_c, axis=0, keepdims=False)
        loss_m, loss_vjp = jax.vjp(lambda a: loss_fn(a, tgt), y)
        (g_seed,) = loss_vjp(jnp.ones_like(loss_m))
        loss_acc = loss_acc + jnp.where(
            jnp.logical_and(is_last, fwd_valid), loss_m, 0.0
        )

        # ---- backward half: microbatch m_b through this stage -----------
        m_b = t - 2 * (S - 1) + stage
        bwd_valid = jnp.logical_and(m_b >= 0, m_b < M)
        m_b_c = jnp.clip(m_b, 0, M - 1)
        saved_in = lax.dynamic_index_in_dim(
            resid, m_b_c % D, axis=0, keepdims=False
        )
        cot = jnp.where(is_last, g_seed, bwd_state)
        _, stage_vjp = jax.vjp(stage_fn, stage_params, saved_in)
        p_bar, x_bar = stage_vjp(cot.astype(y.dtype))
        bmask = bwd_valid.astype(x.dtype)
        grad_acc = jax.tree_util.tree_map(
            lambda acc, g: acc + g * bmask.astype(g.dtype), grad_acc, p_bar
        )

        # ---- shift: activations forward, cotangents backward ------------
        fwd_state = lax.ppermute(y, axis_name, fwd_perm)
        bwd_state = lax.ppermute(x_bar * bmask, axis_name, bwd_perm)
        return fwd_state, bwd_state, resid, grad_acc, loss_acc

    carry0 = (
        jnp.zeros(mb_shape, x.dtype),
        jnp.zeros(mb_shape, x.dtype),
        jnp.zeros((D,) + mb_shape, x.dtype),
        zeros_like_params,
        jnp.zeros((), jnp.float32),
    )
    _, _, _, grads, loss_sum = lax.fori_loop(0, T, tick, carry0)

    # mean over microbatches; loss lives on the last stage -> replicate
    loss = lax.psum(jnp.where(is_last, loss_sum, 0.0), axis_name) / M
    grads = jax.tree_util.tree_map(lambda g: g / M, grads)
    return loss, grads


def make_pipeline_train_fn(
    stage_fn: Callable,
    loss_fn: Callable,
    mesh,
    axis_name: str = "pp",
    schedule: str = "1f1b",
    jit: bool = True,
):
    """Jit-ready pipelined train fn: (stacked_params, x_mb, y_mb) -> (loss, grads).

    `schedule` picks the torch-pipelining-shaped runtime:
      * "1f1b" — `pipeline_train_1f1b` (O(S) activation memory).
      * "gpipe" — `jax.grad` through the GPipe forward (XLA schedules the
        backward; O(M) activation memory).
    Grads come back stage-stacked on the leading dim, matching the
    stacked-params layout, so `optax` updates apply directly.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    if schedule not in ("1f1b", "gpipe"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")

    jmesh = getattr(mesh, "jax_mesh", mesh)
    from .._compat import shard_map_fn

    if schedule == "gpipe":

        def train(stacked_params, x, targets):
            def loss_of(p):
                fwd = make_pipeline_fn(stage_fn, mesh, axis_name, jit=False)
                out = fwd(p, x)
                import jax.numpy as jnp

                losses = jax.vmap(loss_fn)(out, targets)
                return jnp.mean(losses)

            loss, grads = jax.value_and_grad(loss_of)(stacked_params)
            return loss, grads

        return jax.jit(train) if jit else train

    def per_stage(p, x, targets):
        local = jax.tree_util.tree_map(lambda l: l[0], p)
        loss, grads = pipeline_train_1f1b(
            stage_fn, loss_fn, local, x, targets, axis_name
        )
        # restore the leading stage dim so out_spec P(axis) re-stacks
        return loss, jax.tree_util.tree_map(lambda g: g[None], grads)

    mapped = shard_map_fn(
        per_stage,
        mesh=jmesh,
        in_specs=(P(axis_name), P(), P()),
        out_specs=(P(), P(axis_name)),
    )
    return jax.jit(mapped) if jit else mapped


def stack_stage_params(per_stage_params):
    """Stack S per-stage pytrees on a new leading dim (to shard over pp)."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *per_stage_params
    )


def make_pipeline_fn(
    stage_fn: Callable,
    mesh,
    axis_name: str = "pp",
    jit: bool = True,
    virtual_stages: int = 1,
):
    """Wrap `pipeline_apply` into a jit-ready global-view callable.

    Returned fn(stacked_params, x) takes stage-stacked params
    (leading dim S — or V*S in stage order when ``virtual_stages=V`` —
    sharded over ``pp``) and microbatched input (M, mb, ...)
    (replicated), and returns (M, mb, ...) outputs (replicated).
    """
    import jax
    from jax.sharding import PartitionSpec as P

    jmesh = getattr(mesh, "jax_mesh", mesh)
    S = jmesh.shape[axis_name]
    from .._compat import shard_map_fn

    if virtual_stages > 1:
        V = virtual_stages

        def consume_chunks(p, x):
            # (V, 1, ...) per-device slice -> (V, ...) chunk stack
            local = jax.tree_util.tree_map(lambda l: l[:, 0], p)
            return pipeline_apply_interleaved(stage_fn, local, x, axis_name)

        mapped = shard_map_fn(
            consume_chunks,
            mesh=jmesh,
            in_specs=(P(None, axis_name), P()),
            out_specs=P(),
        )

        def reshaped(stacked_params, x):
            # stage-ordered (V*S, ...) -> (V, S, ...): dim 1 shards over pp
            # so device i holds global stages {v*S + i} — the interleaved
            # round-robin placement.
            p = jax.tree_util.tree_map(
                lambda l: l.reshape((V, S) + l.shape[1:]), stacked_params
            )
            return mapped(p, x)

        return jax.jit(reshaped) if jit else reshaped

    def consume_stage_dim(p, x):
        # shard_map hands each stage a (1, ...) slice; drop the stage dim
        import jax as _jax

        local = _jax.tree_util.tree_map(lambda l: l[0], p)
        return pipeline_apply(stage_fn, local, x, axis_name)

    mapped = shard_map_fn(
        consume_stage_dim,
        mesh=jmesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
    )
    return jax.jit(mapped) if jit else mapped


def split_microbatches(x, num_microbatches: int):
    """(B, ...) -> (M, B/M, ...) microbatch view."""
    B = x.shape[0]
    if B % num_microbatches != 0:
        raise ValueError(f"batch {B} not divisible by microbatches {num_microbatches}")
    return x.reshape((num_microbatches, B // num_microbatches) + x.shape[1:])


def merge_microbatches(y):
    """(M, mb, ...) -> (B, ...)."""
    return y.reshape((y.shape[0] * y.shape[1],) + y.shape[2:])
