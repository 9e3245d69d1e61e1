"""Activation checkpointing — torch `checkpoint_wrapper` parity, and the
ladder of what a model's per-block `jax.checkpoint` keeps.

Torch wraps modules (`torch/distributed/algorithms/_checkpoint/
checkpoint_wrapper.py`) so their activations are recomputed in backward.
The TPU-native mechanism is `jax.checkpoint` (remat) with a POLICY
choosing what to save — richer than torch's binary wrap/no-wrap because
XLA can keep the cheap-to-store, expensive-to-recompute values (e.g.
matmul results) and recompute the rest. Two seams:

* `checkpoint_wrapper` / `apply_activation_checkpointing`: the
  torch-shaped functional form for arbitrary fns, under one of the
  stock policies in `_POLICIES`. Which of them pays depends on the
  memory left: none is "the best trade" in general.
* the LADDER (below): `TransformerConfig(remat=True)` wraps each block in
  `jax.checkpoint`, and what the block keeps is one of a few fixed
  rungs of `save_only_these_names`, over values the model names where it
  makes them (`jax.ad_checkpoint.checkpoint_name`: nothing outside a
  `jax.checkpoint`). No option picks the rung: a trainer hands the
  program it builds to `fitted`, and at the first call the TPU compiler
  is asked, richest rung first, what the step holds
  (`memory_analysis()`: arguments, outputs and temporaries, so the same
  arguments always give the same rung); the first that fits the device's
  limit less a margin is the step. Without a trainer, or on a device
  that reports no memory limit, a block keeps nothing, as
  `nn.remat(Block)` always did. The trainers' own `remat=` flags
  (`jax.checkpoint` around the whole objective) are another thing and
  keep their meaning.
"""

from __future__ import annotations

import functools
import sys
import threading
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

_POLICIES = {
    # recompute everything (torch checkpoint_wrapper semantics)
    "nothing": "nothing_saveable",
    # save matmul/einsum outputs, recompute elementwise
    "dots": "dots_saveable",
    "dots_no_batch": "dots_with_no_batch_dims_saveable",
    # save everything = no remat (identity wrap, for A/B comparisons)
    "everything": "everything_saveable",
}


def checkpoint_wrapper(
    fn: Callable,
    policy: str = "nothing",
    prevent_cse: bool = True,
    static_argnums=(),
) -> Callable:
    """torch `checkpoint_wrapper(module)` for functions: returns `fn`
    rematerialized under the named save policy (see `_POLICIES`)."""
    import jax

    if policy not in _POLICIES:
        raise ValueError(
            f"unknown checkpoint policy {policy!r}; one of {sorted(_POLICIES)}"
        )
    pol = getattr(jax.checkpoint_policies, _POLICIES[policy])
    return jax.checkpoint(
        fn, policy=pol, prevent_cse=prevent_cse, static_argnums=static_argnums
    )


def apply_activation_checkpointing(
    apply_fn: Callable,
    check_fn: Optional[Callable[[str], bool]] = None,
    policy: str = "nothing",
    **static_kwargs,
) -> Callable:
    """torch `apply_activation_checkpointing(model, check_fn=...)` shape:
    wrap a flax `apply` so the whole forward is rematerialized.

    Python-level flags (`train=True`, `deterministic=False`, ...) must be
    STATIC under `jax.checkpoint` — flax Dropout branches on them — so
    pass them here as keyword arguments and they are bound before the
    remat wrap: ``fwd = apply_activation_checkpointing(m.apply,
    train=True)``. Per-layer selection belongs model-side
    (`TransformerConfig(remat=True)` remats each Block); `check_fn` is
    accepted for API parity and must be None here — selective wrapping of
    arbitrary submodules has no functional analog at this seam."""
    if check_fn is not None:
        raise NotImplementedError(
            "per-submodule selection: use the model's remat config "
            "(e.g. TransformerConfig(remat=True)) instead"
        )
    if static_kwargs:
        base = lambda *args: apply_fn(*args, **static_kwargs)
    else:
        base = apply_fn
    return checkpoint_wrapper(base, policy=policy)


# ---------------------------------------------------------------------------
# the ladder: what a per-block `jax.checkpoint` keeps
# ---------------------------------------------------------------------------

# names, bound where the value is made
FLASH_OUT, FLASH_LSE = "flash_out", "flash_lse"  # ops/flash_attention.py
ATTN_Q, ATTN_K, ATTN_V = "attn_q", "attn_k", "attn_v"  # after rope, before the GQA repeat
BLOCK_MID = "block_mid"  # x + attention, the MLP half's input
MLP_GATE, MLP_UP = "mlp_gate", "mlp_up"

# cheapest bytes per recomputed millisecond first; each rung holds the one
# below it. 0: nothing (the backward runs the block's forward again);
# 1: the flash kernel's output and log-sum-exp (no second kernel run);
# 2: + the kernel's q/k/v and the mid residual (no second projection or
# rope); 3: + the MLP's gate and up products. Left recomputed at the top:
# norms, the GQA repeat, silu(gate) * up.
LADDER: Tuple[Tuple[str, ...], ...] = (
    (),
    (FLASH_OUT, FLASH_LSE),
    (FLASH_OUT, FLASH_LSE, ATTN_Q, ATTN_K, ATTN_V, BLOCK_MID),
    (FLASH_OUT, FLASH_LSE, ATTN_Q, ATTN_K, ATTN_V, BLOCK_MID, MLP_GATE, MLP_UP),
)

# of the device's limit, left empty beside the compiler's count of a step:
# what the count cannot see (allocator fragmentation, buffers of the
# process that are not the step's arguments, a program that runs beside it)
MARGIN_SHARE = 1 / 16


def save_policy(rung: int):
    """The `jax.checkpoint` policy of a rung; None for rung 0, so that a
    block kept at "nothing" is wrapped exactly as it was before rungs."""
    import jax

    if rung == 0:
        return None
    return jax.checkpoint_policies.save_only_these_names(*LADDER[rung])


class _Tracing(threading.local):
    """The `at_rung` a step is being traced under on this thread, else None."""

    at = None


_tracing = _Tracing()


class at_rung:
    """`at_rung(r)(fn)` is `fn` under the same name, traced with a model's
    blocks keeping rung `r`; `.asked` turns true when a model read it
    (`rung`), so a step with no per-block remat is told apart."""

    def __init__(self, rung: int):
        self.rung, self.asked = rung, False

    def __call__(self, fn: Callable) -> Callable:
        @functools.wraps(fn)
        def traced(*args, **kwargs):
            prev, _tracing.at = _tracing.at, self
            try:
                return fn(*args, **kwargs)
            finally:
                _tracing.at = prev

        return traced


def rung() -> int:
    """The rung a model's blocks keep in this trace: the one the trainer's
    step is being traced at, and 0 with no such trace around."""
    at = _tracing.at
    if at is None:
        return 0
    at.asked = True
    return at.rung


@dataclass(frozen=True)
class RematPlan:
    """The rung a step took and the numbers it took it from."""

    rung: int
    limit_bytes: int  # the device's `bytes_limit`
    # (rung, bytes a chip holds by the compiler's count; None where the
    # compiler itself ran out) of every rung compiled, richest first
    held: Tuple[Tuple[int, Optional[int]], ...]

    @property
    def budget_bytes(self) -> int:
        return self.limit_bytes - int(MARGIN_SHARE * self.limit_bytes)

    def __str__(self):
        gb = lambda b: "more than the compiler can place" if b is None else f"{b / 1e9:.2f} GB"
        return (
            f"remat: rung {self.rung} of {len(LADDER) - 1} keeps "
            f"[{', '.join(LADDER[self.rung]) or 'nothing'}] a block; a chip "
            f"holds, by the compiler's count, "
            f"{', '.join(f'at rung {r} {gb(b)}' for r, b in self.held)}; the "
            f"step fits under {gb(self.budget_bytes)} (limit "
            f"{gb(self.limit_bytes)} less a margin of 1/{round(1 / MARGIN_SHARE)})"
        )


def device_limit_bytes(devices) -> Optional[int]:
    """The smallest `memory_stats()["bytes_limit"]` of `devices`. None
    where one reports no limit (the CPU) or cannot be asked (a described
    device of a deviceless compile, another process's device), and in a
    gang of several processes: the ranks of one SPMD program must lower
    the same text, and each would ask its own chips."""
    import jax

    if jax.process_count() > 1:
        return None
    limits = []
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except jax.errors.JaxRuntimeError:  # not addressable
            return None
        if not stats.get("bytes_limit"):
            return None
        limits.append(int(stats["bytes_limit"]))
    return min(limits, default=None)


def held_bytes(compiled) -> int:
    """What a chip holds while `compiled` runs, by the compiler's count:
    arguments and outputs (less the outputs written over donated
    arguments), temporaries and the program itself."""
    m = compiled.memory_analysis()
    return int(
        m.argument_size_in_bytes + m.output_size_in_bytes
        - m.alias_size_in_bytes + m.temp_size_in_bytes
        + m.generated_code_size_in_bytes
    )


def fit(build: Callable, limit_bytes: int, args):
    """(program, plan): the richest rung of `LADDER` at which the step
    `build` makes fits `limit_bytes` less the margin, for `args` (arrays
    or `ShapeDtypeStruct`s). `build(trace)` returns the `jax.jit` of the
    step with `trace` applied to the function it traces. Richest first,
    each rung is lowered and compiled and the compiler's count read
    (`held_bytes`); rung 0, what `nn.remat(Block)` always ran, is taken
    whatever it counts. The program returned is compiled: calling it
    compiles nothing again. plan is None where no model read the rung
    (no per-block remat in the step): then the first program is it."""
    import jax

    budget = limit_bytes - int(MARGIN_SHARE * limit_bytes)
    held = []
    for r in reversed(range(len(LADDER))):
        trace = at_rung(r)
        program = build(trace)
        try:
            n = held_bytes(program.lower(*args).compile())
        except jax.errors.JaxRuntimeError as e:
            if r == 0 or "RESOURCE_EXHAUSTED" not in str(e):
                raise
            n = None  # the compiler could not place it at all
        if not trace.asked:
            return program, None
        held.append((r, n))
        if r == 0 or (n is not None and n <= budget):
            return program, RematPlan(r, limit_bytes, tuple(held))


class _Fitted:
    """A step that is fitted at its first call (or `lower`), then is the
    program `fit` chose. `remat_plan` says which and from what numbers
    (None before, and for a step with no per-block remat)."""

    def __init__(self, build, limit_bytes):
        self._build, self._limit = build, limit_bytes
        self._program = None
        self.remat_plan = None

    def _fit(self, args):
        if self._program is None:
            self._program, self.remat_plan = fit(self._build, self._limit, args)
            if self.remat_plan is not None:
                print(self.remat_plan, file=sys.stderr, flush=True)
        return self._program

    def __call__(self, *args):
        return (self._program or self._fit(args))(*args)

    def lower(self, *args):
        return self._fit(args).lower(*args)


def fitted(build: Callable, devices):
    """The step a trainer hands out. `build(trace)` as in `fit`. Where
    `devices` report a memory limit: a step that takes, at its first
    call, the richest rung the compiler says fits. Elsewhere (the CPU, a
    deviceless compile): `build`'s own program with nothing applied,
    which keeps nothing a block and has no `remat_plan` to show."""
    limit = device_limit_bytes(devices)
    if limit is None:
        program = build(lambda fn: fn)
        program.remat_plan = None
        return program
    return _Fitted(build, limit)
