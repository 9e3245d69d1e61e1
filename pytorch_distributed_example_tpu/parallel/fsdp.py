"""FSDP-equivalent: fully-sharded data parallelism as GSPMD param sharding.

Parity surface: `torch/distributed/fsdp/` (SURVEY.md §2.3 row "DP sharded" —
BASELINE.json stretch config #5 "FSDP full-shard → GSPMD"). The TPU-native
design: parameters live sharded over the ``fsdp`` mesh axis
(`NamedSharding`, dim-0 sharded); the train step is jit-compiled with those
shardings, and XLA's SPMD partitioner inserts the per-layer all-gather
(forward/backward) and reduce-scatter (grad) that torch FSDP schedules by
hand — overlapped by XLA's latency-hiding scheduler rather than by
FSDP's prefetch machinery.

ZeRO stages map as: params sharded = ZeRO-3 (default); `shard_optimizer_only`
(params replicated, optimizer state sharded) = ZeRO-1.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Callable, Optional, Sequence

from ..utils import remat as _remat
from . import sharding as shd


class FSDPModule:
    """A model whose params are fully sharded over a mesh axis.

    Usage::

        mod = fully_shard(model, params, mesh, axis="fsdp")
        step = mod.make_train_step(optimizer, loss_fn)
        params, opt_state, loss = step(mod.params, opt_state, x, y)
    """

    def __init__(self, module, params, mesh, axis: str, specs, data_axes):
        self.module = module
        self.params = params
        self.mesh = mesh
        self.axis = axis
        self.param_specs = specs
        self.data_axes = tuple(data_axes)
        self.head_axes = head_axes_from_specs(specs)

    def __call__(self, x, *args, **kwargs):
        with _kernel_partition(self.mesh, self.data_axes, self.head_axes):
            return self.module.apply(self.params, x, *args, **kwargs)

    def make_train_step(
        self,
        optimizer,
        loss_fn: Callable,
        has_rng: bool = False,
        remat: bool = False,
        donate: bool = True,
    ):
        return make_fsdp_train_step(
            self.module.apply,
            loss_fn,
            optimizer,
            self.mesh,
            self.param_specs,
            data_axes=self.data_axes,
            has_rng=has_rng,
            remat=remat,
            donate=donate,
        )

    def gather_params(self):
        """Full (unsharded) params on host — rank-0-checkpoint substrate."""
        import jax

        return jax.tree_util.tree_map(lambda x: jax.device_get(x), self.params)


def fully_shard(
    module,
    params,
    mesh,
    axis: str = "fsdp",
    rules: Optional[Sequence[shd.Rule]] = None,
    data_axes: Sequence[str] = ("dp", "fsdp"),
) -> FSDPModule:
    """Shard ``params`` dim-0 over ``mesh[axis]`` (torch `fully_shard` shape).

    ``rules`` overrides the catch-all dim-0 rule for custom layouts (e.g.
    combined fsdp+tp). Leaves whose dim 0 is not divisible by the axis size
    stay replicated (FSDP's small-param behavior).
    """
    jmesh = getattr(mesh, "jax_mesh", mesh)
    if axis not in dict(jmesh.shape):
        raise ValueError(f"mesh has no axis {axis!r}: {tuple(dict(jmesh.shape))}")
    sharded, specs = shd.shard_params(params, jmesh, rules or shd.fsdp_rules(axis))
    present = [a for a in data_axes if a in dict(jmesh.shape)]
    return FSDPModule(module, sharded, jmesh, axis, specs, present or (axis,))


_Q_PROJ = re.compile(r"(^|/)q_proj/kernel$")


def head_axes_from_specs(param_specs):
    """The mesh axes attention heads are sharded over — what a Pallas
    kernel inside a GSPMD step must be told (`ops.partitioned_over`). Only
    the sharding rules know it, and a wrong answer makes GSPMD reshard
    q/k/v around the kernel, so it is read off ``param_specs`` and never
    guessed from the mesh: the axes on the output dim of every
    ``q_proj/kernel`` ((d_model, heads * head_dim), so colwise = by head;
    K/V are repeated to the query heads before the kernel, so the query
    projection alone decides). No such leaf, or an unsharded output dim,
    means heads are whole on every device: ``()``.
    """
    import jax

    seen = {}
    for kp, spec in jax.tree_util.tree_flatten_with_path(param_specs)[0]:
        path = shd.path_of(kp)
        if _Q_PROJ.search(path):
            ax = spec[1] if len(spec) > 1 else None
            axes = () if ax is None else (ax,) if isinstance(ax, str) else tuple(ax)
            seen.setdefault(axes, path)
    if len(seen) > 1:
        raise ValueError(
            "q_proj kernels disagree on the axes that shard heads: "
            + ", ".join(f"{p} -> {a}" for a, p in seen.items())
        )
    return next(iter(seen), ())


def _kernel_partition(jmesh, data_axes, head_axes):
    """The layout Pallas kernels run under inside a GSPMD step on
    ``jmesh`` (`ops.partitioned_over`): batch rows over the data axes,
    heads over ``head_axes`` (`head_axes_from_specs`), whole over every other
    axis of the mesh. ``data_axes`` are axes of the mesh (both callers
    have already dropped absent ones)."""
    from ..ops import partitioned_over

    both = set(data_axes) & set(head_axes)
    if both:
        raise ValueError(
            f"mesh axes {sorted(both)} are both data axes {tuple(data_axes)} "
            f"and head axes {tuple(head_axes)}; a tensor dimension pair "
            "(batch, heads) cannot share a mesh axis"
        )
    return partitioned_over(jmesh, data_axes, head_axes)


def _batch_spec(jmesh, data_axes):
    from jax.sharding import PartitionSpec as P

    data_axes = tuple(a for a in data_axes if a in dict(jmesh.shape))
    if not data_axes:
        raise ValueError(
            f"none of data_axes present in mesh axes {tuple(dict(jmesh.shape))}; "
            "pass data_axes matching your mesh (e.g. data_axes=('fsdp',))"
        )
    return P(data_axes if len(data_axes) > 1 else data_axes[0])


def _make_constrained_train_step(
    apply_fn: Callable,
    loss_fn: Callable,
    optimizer,
    jmesh,
    batch_spec,
    constrain_grads: Callable,
    constrain_opt_state: Optional[Callable],
    constrain_params: Callable,
    param_sharding,
    has_rng: bool,
    remat: bool,
    donate: bool,
    comm_hook: Optional[Callable] = None,
    hook_axis: Optional[str] = None,
    head_axes: Sequence[str] = (),
):
    """Shared fwd/bwd/update scaffold for the ZeRO family.

    The stages only differ in which sharding constraints they pin on
    grads / optimizer state / updated params (and the params' jit
    sharding); everything else — rng threading, remat, donation — lives
    here once.

    `comm_hook` (requires replicated params, i.e. the ZeRO-2 layout and
    `hook_axis` naming the one data axis): the gradient reduction runs
    MANUALLY inside a `shard_map` region — per-device grads from the
    local batch shard, then `hook(grads, axis)` (e.g. the blockwise
    wire-quantized all-reduce) — instead of falling out of GSPMD, which
    offers no seam to quantize its implicit reduction. Grads exit the
    region replicated; the stage's sharding constraints (sharded
    optimizer update, update all-gather) apply unchanged downstream.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .._compat import shard_map_fn

    data_axes = batch_spec[0]
    if isinstance(data_axes, str):
        data_axes = (data_axes,)

    def step(params, opt_state, x, y, *rng):
        def objective(p, xl, yl, key):
            if has_rng:
                fwd = lambda pp: apply_fn(pp, xl, rngs={"dropout": key})
            else:
                fwd = lambda pp: apply_fn(pp, xl)
            if remat:
                fwd = jax.checkpoint(fwd)
            logits = fwd(p)
            with jax.named_scope("loss"):
                return loss_fn(logits, yl)

        if comm_hook is None:
            # GSPMD partitions everything in this branch except Pallas
            # kernels, which need their layout spelled out
            with _kernel_partition(jmesh, data_axes, head_axes):
                loss, grads = jax.value_and_grad(
                    lambda p: objective(p, x, y, rng[0] if has_rng else None)
                )(params)
        else:
            from jax import lax

            def local(p, xl, yl):
                # per-shard dropout key: every device sees its own
                # batch shard, so the closed-over key must be folded
                # with the device's axis index — otherwise all W ranks
                # draw the SAME mask pattern (correlated dropout, and
                # different semantics from the comm_hook=None path)
                key = (
                    jax.random.fold_in(rng[0], lax.axis_index(hook_axis))
                    if has_rng
                    else None
                )
                loss, g = jax.value_and_grad(
                    lambda pp: objective(pp, xl, yl, key)
                )(p)
                with jax.named_scope("grad_reduce"):
                    g = comm_hook(g, hook_axis)
                with jax.named_scope("loss"):
                    loss = lax.pmean(loss, hook_axis)
                return loss, g

            loss, grads = shard_map_fn(
                local,
                mesh=jmesh,
                in_specs=(P(), batch_spec, batch_spec),
                out_specs=(P(), P()),
            )(params, x, y)
        grads = constrain_grads(grads)
        # the sharding constraints stay outside the scope: the collectives
        # GSPMD derives from them are the compiler's, not the update's
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
        if constrain_opt_state is not None:
            opt_state = constrain_opt_state(opt_state, params)
        with jax.named_scope("optimizer"):
            params = jax.tree_util.tree_map(
                lambda p, u: p + u, params, updates
            )
        params = constrain_params(params)
        return params, opt_state, loss

    xshard = NamedSharding(jmesh, batch_spec)
    rep = NamedSharding(jmesh, P())
    # a model with per-block remat keeps, of each block, the most the TPU
    # compiler says the chip has room for (utils/remat.py); `.remat_plan`
    # says which rung and from what counts
    return _remat.fitted(
        lambda trace: jax.jit(
            trace(step),
            in_shardings=(param_sharding, None, xshard, xshard)
            + ((rep,) if has_rng else ()),
            out_shardings=(param_sharding, None, rep),
            donate_argnums=(0, 1) if donate else (),
        ),
        jmesh.devices.flat,
    )


def _check_swu(shard_weight_update: str) -> bool:
    """Resolve the tri-state `shard_weight_update` flag for the GSPMD
    family (here "auto" and "force" coincide: the mesh axis exists by
    construction, so sharding is always possible)."""
    if shard_weight_update not in ("auto", "off", "force"):
        raise ValueError(
            f"shard_weight_update={shard_weight_update!r}; expected "
            "'auto', 'off', or 'force'"
        )
    return shard_weight_update != "off"


def make_fsdp_train_step(
    apply_fn: Callable,
    loss_fn: Callable,
    optimizer,
    mesh,
    param_specs,
    data_axes: Sequence[str] = ("dp", "fsdp"),
    has_rng: bool = False,
    remat: bool = False,
    donate: bool = True,
    shard_weight_update: str = "auto",
):
    """Compile the FSDP (ZeRO-3) train step: batch split over data axes,
    params sharded per ``param_specs``; XLA GSPMD materializes the
    per-layer gather/scatter. Pallas attention kernels in ``apply_fn``
    run per device, heads over the axes ``param_specs`` shard them by
    (`head_axes_from_specs`).

    `shard_weight_update="auto"` (default) pins the optimizer state to
    the PARAM layout explicitly (under ZeRO-3 the moments mirror the
    sharded params — the constraint makes that a contract instead of a
    propagation accident) and attaches `step.init_opt_state(params)`.
    "off" constrains the state REPLICATED — the world-x-redundant
    baseline.
    """
    import jax
    from jax.sharding import NamedSharding

    jmesh = getattr(mesh, "jax_mesh", mesh)
    sharded_update = _check_swu(shard_weight_update)
    # grads + updated params stay in the param layout (reduce-scatter
    # falls out of SPMD)
    in_layout = lambda tree: shd.constrain(tree, jmesh, param_specs)
    pshard = jax.tree_util.tree_map(
        lambda s: NamedSharding(jmesh, s), param_specs
    )

    def constrain_state(opt_state, params):
        # optimizer state mirrors the params tree leaf-for-leaf in its
        # moment subtrees; shape-match each state leaf to its param's
        # spec so the moments provably stay in the param layout
        if sharded_update:
            return _constrain_like_params(opt_state, params, jmesh,
                                          param_specs)
        return shd.constrain(
            opt_state, jmesh, shd.replicated_specs(opt_state)
        )

    step = _make_constrained_train_step(
        apply_fn,
        loss_fn,
        optimizer,
        jmesh,
        _batch_spec(jmesh, data_axes),
        constrain_grads=in_layout,
        constrain_opt_state=constrain_state,
        constrain_params=in_layout,
        param_sharding=pshard,
        has_rng=has_rng,
        remat=remat,
        donate=donate,
        head_axes=head_axes_from_specs(param_specs),
    )

    def init_opt_state(params):
        """State born in the layout the step keeps it in: the same
        constraint the step applies, inside the jit that creates it (a
        bare `jit(optimizer.init)` puts every moment whole on device 0 —
        its zeros depend on no sharded input — which costs the full
        unsharded state on one chip and a second compile of the step when
        the re-laid-out state comes back), scalars replicated."""
        from jax.sharding import PartitionSpec as P

        state = jax.jit(lambda p: constrain_state(optimizer.init(p), p))(
            params
        )
        rep = NamedSharding(jmesh, P())
        return jax.tree_util.tree_map(
            lambda l: l if l.ndim else jax.device_put(l, rep), state
        )

    step.init_opt_state = init_opt_state
    step.weight_update_sharded = sharded_update
    return step


def _constrain_like_params(opt_state, params, jmesh, param_specs):
    """Constrain opt-state leaves to their OWN param's spec by tree-path
    suffix: optax moment subtrees (mu/nu/trace) embed the full params
    tree, so a state leaf's path ends with its param's path — matching
    by path (shape as a guard) keeps q_proj and o_proj moments in their
    respective layouts even when the kernels share a shape with
    transposed specs (the Megatron colwise/rowwise pair). Unmatched
    non-scalar leaves replicate (step counts, schedule state)."""
    import jax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = jax.tree_util.tree_leaves(param_specs)
    by_path = [
        (shd.path_of(kp), tuple(leaf.shape), spec)
        for (kp, leaf), spec in zip(flat_p, flat_s)
    ]

    def one(kp, leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim < 1:
            return leaf
        path = shd.path_of(kp)
        spec, best = P(), -1
        for ppath, pshape, pspec in by_path:
            # anchor on a path-COMPONENT boundary ('mu/up_proj/kernel'
            # must not string-match 'proj/kernel') and keep the longest
            # suffix, so nested prefixes resolve to the nearest param
            if tuple(leaf.shape) == pshape and (
                path == ppath or path.endswith("/" + ppath)
            ) and len(ppath) > best:
                spec, best = pspec, len(ppath)
        return lax.with_sharding_constraint(leaf, NamedSharding(jmesh, spec))

    return jax.tree_util.tree_map_with_path(one, opt_state)


def make_zero2_train_step(
    apply_fn: Callable,
    loss_fn: Callable,
    optimizer,
    mesh,
    axis: str = "fsdp",
    data_axes: Sequence[str] = ("dp", "fsdp"),
    has_rng: bool = False,
    remat: bool = False,
    donate: bool = True,
    comm_hook: Optional[Callable] = None,
    shard_weight_update: str = "auto",
):
    """ZeRO-2: params REPLICATED, gradients + optimizer state SHARDED.

    Parity: DeepSpeed/torch ZeRO stage 2 (grad partitioning on top of
    ZeRO-1's optimizer-state partitioning). GSPMD shape: the backward's
    gradients are constrained dim-0 sharded over ``axis`` — the SPMD
    partitioner lowers the grad reduction to reduce-scatter instead of
    all-reduce — the optimizer update runs on the 1/W shard, and adding
    the (sharded) updates back to the replicated params makes XLA emit
    exactly one all-gather of the UPDATES. Per-step wire cost equals
    DDP's allreduce (reduce-scatter + all-gather), but optimizer math
    and its state are 1/W per device.

    `comm_hook` is the FSDP face of the gradient-compression hooks
    (`comm_hooks.blockwise_quant_hook(error_feedback=False)` being the
    wire-quantized one): the grad reduction moves into an explicit
    shard_map region and runs `hook(grads, axis)` there (GSPMD's
    implicit reduction has no seam to narrow), cutting the grad-phase
    wire bytes to the hook's wire width; the update all-gather stays
    full-precision. STATELESS hooks only — this step's fixed
    ``(params, opt_state, x, y)`` signature cannot thread a state
    pytree; error-feedback hooks belong on `make_ddp_train_step`.
    Requires exactly one of `data_axes` present in the mesh (the hook
    receives one axis name). ZeRO-3 (`make_fsdp_train_step`) takes no
    hook: its params are sharded, so they cannot ride a replicated
    shard_map region without un-sharding them.

    `shard_weight_update="auto"` (default) IS the ZeRO-2 semantics
    described above, with the opt-in `shard_optimizer_only` placement
    internalized as `step.init_opt_state(params)`; "off" reverts to the
    replicated update (grads all-reduced, state replicated — a GSPMD
    DDP step).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    jmesh = getattr(mesh, "jax_mesh", mesh)
    sharded_update = _check_swu(shard_weight_update)
    constrain_dim0 = lambda tree: shd.constrain_dim0(tree, jmesh, axis)
    replicate = lambda tree: shd.constrain(
        tree, jmesh, shd.replicated_specs(tree)
    )

    if comm_hook is None:
        # planner-aware default: with the traced planner on, the grad
        # reduction moves into the explicit shard_map region and takes
        # the agreed schedule table's per-bucket winner
        # (plan/traced.py — probe outside the trace, prepared below at
        # first call); planner off keeps the GSPMD implicit reduction
        # exactly as before
        from ..plan import traced

        if traced.enabled():
            present = [a for a in data_axes if a in dict(jmesh.shape)]
            if len(present) == 1:
                from . import comm_hooks

                comm_hook = comm_hooks.planner_hook()

    hook_axis = None
    if comm_hook is not None:
        if hasattr(comm_hook, "init") and hasattr(comm_hook, "apply"):
            raise NotImplementedError(
                "stateful comm hooks (error feedback / PowerSGD) thread "
                "a state pytree through the step; the ZeRO-2 signature "
                "cannot — pass a stateless hook (e.g. "
                "blockwise_quant_hook(error_feedback=False)) or use "
                "make_ddp_train_step for the stateful form"
            )
        present = [a for a in data_axes if a in dict(jmesh.shape)]
        if len(present) != 1:
            raise ValueError(
                f"comm_hook needs exactly one data axis in the mesh; "
                f"data_axes {tuple(data_axes)} resolve to {present} on "
                f"mesh axes {tuple(dict(jmesh.shape))}"
            )
        hook_axis = present[0]

    step = _make_constrained_train_step(
        apply_fn,
        loss_fn,
        optimizer,
        jmesh,
        _batch_spec(jmesh, data_axes),
        # sharded: -> reduce-scatter, not all-reduce; state 1/W/device
        constrain_grads=constrain_dim0 if sharded_update else replicate,
        constrain_opt_state=(
            (lambda s, p: constrain_dim0(s))
            if sharded_update
            else (lambda s, p: replicate(s))
        ),
        # replicated output -> one all-gather of the updates
        constrain_params=lambda p: shd.constrain(
            p, jmesh, shd.replicated_specs(p)
        ),
        param_sharding=NamedSharding(jmesh, P()),
        has_rng=has_rng,
        remat=remat,
        donate=donate,
        comm_hook=comm_hook,
        hook_axis=hook_axis,
    )

    if comm_hook is not None and hook_axis is not None:
        # probe + agree the hook's per-leaf schedule buckets on the
        # host BEFORE the first call compiles the step (plan/traced.py:
        # the trace then reads the agreed table purely). Needs a live
        # process group for the planner/store; without one the dispatch
        # seam still honors TDX_PLANNER_FORCE and otherwise warns into
        # the stock lowering.
        inner_step = step
        _prepared = [False]

        # distinct name: this host-side wrapper is never jitted (only
        # ``inner_step`` is), and must not share the jitted function's
        # qualname or static analysis conflates the two trace roots
        def _prepared_step(params, opt_state, x, y, *rng):
            if not _prepared[0]:
                _prepared[0] = True
                from .. import distributed as dist
                from ..plan import traced

                if dist.is_initialized() and traced.enabled():
                    traced.prepare_for_params(
                        dist._get_default_group(), params
                    )
            out = inner_step(params, opt_state, x, y, *rng)
            _prepared_step.remat_plan = inner_step.remat_plan
            return out

        _prepared_step.remat_plan = None
        step = _prepared_step

    def init_opt_state(params):
        """State in the step's native layout: dim-0 sharded over
        ``axis`` under the (default) sharded update — the
        `shard_optimizer_only` placement, now internal — replicated
        under "off"."""
        state = optimizer.init(params)
        if sharded_update:
            return shard_optimizer_only(state, jmesh, axis)
        return state

    step.init_opt_state = init_opt_state
    step.weight_update_sharded = sharded_update
    return step


def shard_optimizer_only(opt_state, mesh, axis: str = "fsdp"):
    """ZeRO-1 layout for the optimizer state: shard its array leaves dim-0
    over ``axis``. Params are untouched (keep them replicated, e.g. via
    `DistributedDataParallel`); returns the re-placed opt_state."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    jmesh = getattr(mesh, "jax_mesh", mesh)
    rules = shd.fsdp_rules(axis)

    def place(x):
        if hasattr(x, "shape") and x.ndim >= 1:
            spec = shd.spec_for("opt", tuple(x.shape), rules, jmesh)
        else:
            spec = P()
        return jax.device_put(x, NamedSharding(jmesh, spec))

    return jax.tree_util.tree_map(place, opt_state)
