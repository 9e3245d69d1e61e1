"""`TDX_TRACE_GUARD=1` — fail-fast guard for host effects under jax tracing.

distlint R011 statically flags host-side effects (blocking store ops,
`faults.fire`, device readbacks) reachable from jit/shard_map trace
roots. This module is the runtime half of that contract, the same way
`schedule.py`'s `TDX_SCHEDULE_CHECK` fingerprint verifier is the runtime
half of R001: with the guard armed, a guarded primitive invoked while
jax is tracing raises a named `TraceGuardError` AT THE OP — instead of
surfacing minutes later as a `TracerArrayConversionError` deep inside a
compiled program, a trace-time side effect that silently runs once
instead of per-step, or (the PR 10 planner-hook shape) a probe blocking
the trace on a tracer value.

Wired into:

  * `faults.fire` — every injection point fires through one choke point,
    so every store client op, rendezvous handler, collective dispatch
    and serve-plane point is covered with its own name;
  * the blocking store primitives that do NOT route through `fire`
    (`HashStore.get`, `FileStore.get`) — named `store.get`.

Off (the default) this is one env read per op. The guard deliberately
lives in its own leaf module with no package imports so `faults`,
`store` and anything else on the dispatch path can use it without
cycles.
"""

from __future__ import annotations

import os

_ENV = "TDX_TRACE_GUARD"

__all__ = ["TraceGuardError", "enabled", "under_tracing", "check"]


class TraceGuardError(RuntimeError):
    """A guarded host-side op ran inside a jax trace (TDX_TRACE_GUARD=1)."""


def enabled() -> bool:
    return os.environ.get(_ENV, "").strip().lower() not in (
        "", "0", "false", "off",
    )


def under_tracing() -> bool:
    """True when jax is currently tracing (jit/shard_map/scan/...).

    The one probe for the package (`plan/planner.py`, `plan/probe.py`
    and `plan/traced.py` come through here): `jax.core.trace_ctx`. A
    jax without it raises `AttributeError` here — a guard that reads a
    missing API as "not tracing" lets every guarded op through."""
    import jax

    return not jax.core.trace_ctx.is_top_level()


def check(op: str) -> None:
    """Raise `TraceGuardError` naming ``op`` when the guard is armed and
    jax is tracing; no-op otherwise."""
    if not enabled():
        return
    if under_tracing():
        raise TraceGuardError(
            f"host-side op `{op}` invoked while jax is tracing "
            "(TDX_TRACE_GUARD=1): a jit/shard_map-traced body must stay "
            "device-pure — this op would block on a tracer or execute "
            "once at trace time instead of every step. Hoist it out of "
            "the traced body (probe outside the trace, agree through the "
            "store, pass the result in) or run without the guard."
        )
