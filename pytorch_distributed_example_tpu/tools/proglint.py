"""proglint — jaxpr-level program-plane analyzer with compile-time
cross-rank schedule agreement (ISSUE 14).

`tools/distlint.py` proves the SOURCE plane cannot diverge (rules
R001–R015 over the project call graph) and the runtime ScheduleVerifier
(`schedule.py`, TDX_SCHEDULE_CHECK=1) catches a divergent EXECUTED
schedule — but only after a collective has been issued. Every hot path
in this repo now lives inside compiled programs (donated decode steps,
ZeRO shard/gather halves, planner shard_map bodies) that neither layer
inspects. proglint closes that gap: it walks the ClosedJaxprs of the
repo's registered compiled programs — recursing through
pjit/shard_map/scan/cond/while/remat/custom-vjp sub-jaxprs — and
extracts a canonical **program fingerprint**: the ordered sequence of
collective eqns (psum, psum_scatter, all_gather, ppermute, all_to_all,
…) with axis names, operand shapes/dtypes and permutations, plus the
donation set and the ACTUAL `input_output_aliases` of the lowered
program.

Rules on top of the fingerprint:

  J001  collective axis name absent from the binding mesh and from the
        project-wide mesh-axis registry (distlint R015's harvest,
        consumed via `distlint.harvested_mesh_axes` — one source of
        truth for both planes)
  J002  ppermute permutation structurally invalid (duplicate
        sources/destinations, out-of-range endpoints) or inconsistent
        with the registered plan artifact's round sequence
  J003  donated argument not actually aliased in the lowered program —
        the silently-dropped donation class (PR 4's 306 ms/step memcpy)
  J004  quantized-wire program carrying a >1-byte payload dtype through
        a collective (the jaxpr pin PR 7 kept test-local, promoted;
        `tests/test_quant.py` asserts through the same helper so the
        pin and the rule can never drift apart)
  J005  cross-rank compiled-schedule agreement — runtime: under
        `TDX_PROGLINT=1` every registered program's fingerprint is
        published through the incarnation-scoped group store before
        first dispatch and a mismatch raises
        `ProgramScheduleMismatchError` naming the first divergent eqn
        (`schedule.agree_program`), turning the run-time hang class
        into a compile-time failure

Register-on-compile seams (`TDX_PROGLINT=1`): `serve/decode.py`
paged programs, `parallel/ddp.py` train steps (replicated and
ZeRO), `plan/driver.py` compiled schedule bodies — each wraps its
jitted program in `instrument()`, which fingerprints on first call and
runs the J005 agreement. The CLI
(`python -m pytorch_distributed_example_tpu.tools.proglint`) builds the
same registered programs on a tiny CPU geometry, runs J001–J004 over
all of them, and reports human/JSON/SARIF with the content-fingerprinted
baseline ratchet shared with distlint (`.proglint-baseline.json`).
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import re
import sys
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .. import traceguard
from ._lintcore import (
    SEVERITIES,
    Finding,
    apply_baseline,
    load_baseline,
    render_report,
    render_sarif,
    write_baseline,
)
from .distlint import harvested_mesh_axes

__all__ = [
    "RULES",
    "COLLECTIVE_PRIMS",
    "CollectiveEqn",
    "ProgramFingerprint",
    "collect_collectives",
    "quantized_wire_violations",
    "fingerprint_traced",
    "fingerprint_program",
    "check_fingerprint",
    "expected_perms_from_plan",
    "armed",
    "instrument",
    "registry",
    "register_fingerprint",
    "build_repo_programs",
    "lint_repo_programs",
    "load_config",
    "main",
]

RULES = {
    "J001": "collective axis name absent from the binding mesh and the "
            "harvested mesh-axis registry",
    "J002": "ppermute permutation invalid or inconsistent with the "
            "registered plan artifact",
    "J003": "donated argument not aliased in the lowered program "
            "(donation silently dropped)",
    "J004": "quantized-wire program moves a >1-byte payload dtype "
            "through a collective",
    "J005": "cross-rank compiled-schedule disagreement (runtime rule: "
            "ProgramScheduleMismatchError at agreement time)",
}

_ENV = "TDX_PROGLINT"

# Collective primitive names across the jax versions this repo supports;
# `psum_scatter` is the canonical name for the reduce_scatter primitive
# (lax.psum_scatter traces to primitive "reduce_scatter").
COLLECTIVE_PRIMS = frozenset({
    "psum",
    "pmax",
    "pmin",
    "ppermute",
    "pshuffle",
    "all_gather",
    "all_gather_invariant",
    "all_to_all",
    "reduce_scatter",
    "psum_scatter",
})
_CANONICAL = {"reduce_scatter": "psum_scatter"}

# eqn params that must agree across ranks but are invisible in
# (primitive, axes, operands) — carried into the descriptor verbatim
_DETAIL_PARAMS = (
    "scatter_dimension",
    "all_gather_dimension",
    "split_axis",
    "concat_axis",
    "tiled",
    "axis_index_groups",
)


# ---------------------------------------------------------------------------
# collective-eqn collection (the shared recursive jaxpr walk)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CollectiveEqn:
    """One collective equation in program order (depth-first)."""

    index: int
    primitive: str                                   # canonical name
    axes: Tuple[str, ...]                            # named mesh axes
    operands: Tuple[Tuple[str, Tuple[int, ...]], ...]  # (dtype, shape)
    perm: Optional[Tuple[Tuple[int, int], ...]] = None
    detail: str = ""

    def descriptor(self) -> str:
        ops = ",".join(
            f"{d}[{'x'.join(str(s) for s in shp)}]"
            for d, shp in self.operands
        )
        base = f"{self.primitive}|axes={','.join(self.axes)}|{ops}"
        if self.perm is not None:
            base += "|perm=" + ";".join(f"{a}>{b}" for a, b in self.perm)
        if self.detail:
            base += f"|{self.detail}"
        return base


def _iter_child_jaxprs(value):
    """Sub-jaxprs hiding in an eqn param: a ClosedJaxpr (pjit, scan,
    remat, custom-vjp), a raw Jaxpr (shard_map), or a CONTAINER of them
    (cond's `branches` tuple) — the container case is what the PR 7
    test-local walker missed."""
    if hasattr(value, "jaxpr"):
        yield value.jaxpr
    elif hasattr(value, "eqns"):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _iter_child_jaxprs(v)


def _axes_of(eq) -> Tuple[str, ...]:
    ax = eq.params.get("axes")
    if ax is None:
        ax = eq.params.get("axis_name")
    if ax is None:
        return ()
    if not isinstance(ax, (tuple, list)):
        ax = (ax,)
    # only NAMED axes participate in J001; positional (vmap) axes are
    # integers and bind no mesh
    return tuple(a for a in ax if isinstance(a, str))


def _eqn_of(eq, index: int) -> CollectiveEqn:
    perm = eq.params.get("perm")
    details = []
    for k in _DETAIL_PARAMS:
        v = eq.params.get(k)
        if v is not None and v is not False:
            details.append(f"{k}={v}")
    return CollectiveEqn(
        index=index,
        primitive=_CANONICAL.get(eq.primitive.name, eq.primitive.name),
        axes=_axes_of(eq),
        operands=tuple(
            (str(v.aval.dtype), tuple(int(d) for d in v.aval.shape))
            for v in eq.invars
            if hasattr(v, "aval") and hasattr(v.aval, "dtype")
        ),
        perm=(
            tuple((int(a), int(b)) for a, b in perm)
            if perm is not None
            else None
        ),
        detail="|".join(details),
    )


def collect_collectives(jaxpr, prims=None) -> List[CollectiveEqn]:
    """Ordered collective eqns of a ClosedJaxpr/Jaxpr, recursing into
    every sub-jaxpr (pjit, shard_map, scan, while, cond branches, remat,
    custom-vjp). The shared walk behind rule J004, the program
    fingerprint, and `tests/test_quant.py`'s wire-dtype pin."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    wanted = COLLECTIVE_PRIMS if prims is None else frozenset(prims)
    out: List[CollectiveEqn] = []

    def walk(j) -> None:
        for eq in j.eqns:
            if eq.primitive.name in wanted:
                out.append(_eqn_of(eq, len(out)))
            for v in eq.params.values():
                for child in _iter_child_jaxprs(v):
                    walk(child)

    walk(inner)
    return out


def quantized_wire_violations(
    eqns: Sequence[CollectiveEqn],
) -> List[Tuple[CollectiveEqn, Tuple[str, Tuple[int, ...]], int]]:
    """Operands violating the quantized-wire contract, as
    (eqn, (dtype, shape), nbytes) triples — rule J004's core, shared
    with the PR 7 wire-dtype pin in tests/test_quant.py.

    Contract: in a wire-quantized program the PAYLOAD moving through
    every collective is a 1-byte dtype; wider operands are legitimate
    only as small sidecars (per-block scales — f32, but a fraction of
    the payload bytes). So: let B be the largest 1-byte collective
    operand in the program; any >1-byte operand at or above B bytes is
    a payload regression, and if NO 1-byte operand exists at all the
    wire is simply unquantized and every >1-byte operand is flagged
    (the old `quantize_hook` psum'd int32 — zero savings — exactly this
    shape)."""
    import numpy as np

    sized = []
    best_1byte = 0
    for eq in eqns:
        for dt, shape in eq.operands:
            item = np.dtype(dt).itemsize
            n = 1
            for s in shape:
                n *= int(s)
            nbytes = n * item
            sized.append((eq, dt, shape, nbytes, item))
            if item == 1:
                best_1byte = max(best_1byte, nbytes)
    out = []
    for eq, dt, shape, nbytes, item in sized:
        if item <= 1:
            continue
        if best_1byte == 0 or nbytes >= best_1byte:
            out.append((eq, (dt, shape), nbytes))
    return out


# ---------------------------------------------------------------------------
# program fingerprints
# ---------------------------------------------------------------------------


@dataclass
class ProgramFingerprint:
    """Canonical identity of one compiled program: the ordered
    collective sequence plus the donation/aliasing set. `digest` is what
    ranks agree on (J005); `canonical()` is what the golden corpus
    ratchets."""

    name: str
    path: str = ""
    eqns: Tuple[CollectiveEqn, ...] = ()
    donated: Tuple[int, ...] = ()       # flat donated arg indices
    aliased: Tuple[int, ...] = ()       # flat indices actually aliased
    arg_labels: Tuple[str, ...] = ()    # flat arg tree-path labels
    mesh_axes: Tuple[str, ...] = ()     # the binding mesh's axis names
    world: Optional[int] = None
    alias_checked: bool = True          # False: no lowering available

    def eqn_descriptors(self) -> List[str]:
        return [e.descriptor() for e in self.eqns]

    def canonical(self) -> Dict[str, Any]:
        doc: Dict[str, Any] = {
            "name": self.name,
            "eqns": self.eqn_descriptors(),
            "donated": sorted(self.donated),
            "aliased": sorted(self.aliased) if self.alias_checked else None,
            "mesh_axes": list(self.mesh_axes),
            "world": self.world,
        }
        doc["digest"] = self.digest
        return doc

    @property
    def digest(self) -> str:
        body = json.dumps(
            {
                "eqns": self.eqn_descriptors(),
                "donated": sorted(self.donated),
                "aliased": (
                    sorted(self.aliased) if self.alias_checked else None
                ),
            },
            sort_keys=True,
        )
        return hashlib.sha256(body.encode()).hexdigest()[:32]


def _aliased_flat_args(text: str) -> Tuple[int, List[int]]:
    """(arg count, aliased arg indices) of the lowered StableHLO's
    @main signature — indices in the LOWERED numbering. An arg counts as
    alias-declared via `tf.aliasing_output` (aliasing pinned at lowering
    — the plain-jit decode programs) or `jax.buffer_donor` (sharded
    lowerings: the donation is declared and XLA picks the concrete
    aliasing at compile). A donated arg carrying NEITHER was silently
    dropped at lowering — e.g. a donated buffer the program no longer
    returns — and its update runs as a copy every step (J003).

    CAUTION: jit's default keep_unused=False PRUNES unused args from
    the lowering, so `%argN` here does NOT number the traced args —
    callers map back through `_kept_var_idx`."""
    m = re.search(r"@main\(", text)
    if m is None:
        return 0, []
    seg = text[m.end():]
    end = seg.find("->")
    if end >= 0:
        seg = seg[:end]
    out = []
    marks = list(re.finditer(r"%arg(\d+):", seg))
    for i, mk in enumerate(marks):
        stop = marks[i + 1].start() if i + 1 < len(marks) else len(seg)
        attrs = seg[mk.end():stop]
        if "tf.aliasing_output" in attrs or "jax.buffer_donor" in attrs:
            out.append(int(mk.group(1)))
    return len(marks), out


def _kept_var_idx(lowered) -> Optional[List[int]]:
    """Sorted original-flat-arg indices the lowering KEPT (jit prunes
    unused args by default); None when the internals are unavailable."""
    try:
        kept = lowered._lowering.compile_args.get("kept_var_idx")
    except AttributeError:
        return None
    if kept is None:
        return None
    return sorted(int(i) for i in kept)


def _donation_of(traced) -> Tuple[Tuple[int, ...], Tuple[str, ...]]:
    """(donated flat indices, per-flat-arg tree-path labels) from a
    jax.stages.Traced's args_info."""
    import jax

    info = getattr(traced, "args_info", None)
    if info is None:
        return (), ()
    pairs, _ = jax.tree_util.tree_flatten_with_path(
        info, is_leaf=lambda l: hasattr(l, "donated")
    )
    donated = tuple(
        i for i, (_, leaf) in enumerate(pairs)
        if getattr(leaf, "donated", False)
    )
    labels = tuple(
        f"arg{jax.tree_util.keystr(p)}" for p, _ in pairs
    )
    return donated, labels


def fingerprint_traced(
    name: str,
    traced,
    *,
    path: str = "",
    mesh_axes: Sequence[str] = (),
    world: Optional[int] = None,
    with_lowering: bool = True,
) -> ProgramFingerprint:
    """Fingerprint a `jitted.trace(*args)` result: collective eqns from
    the jaxpr, the donation set from args_info, and — when lowering is
    available — the ACTUAL alias set from the StableHLO text."""
    eqns = tuple(collect_collectives(traced.jaxpr))
    donated, labels = _donation_of(traced)
    n_flat = len(labels) or len(traced.jaxpr.in_avals)
    aliased: Tuple[int, ...] = ()
    alias_checked = False
    if with_lowering:
        lowered = text = None
        try:
            lowered = traced.lower()
            text = lowered.as_text()
        except Exception:  # pragma: no cover - lowering unavailable
            text = None
        if text is not None:
            n_main, low_aliased = _aliased_flat_args(text)
            kept = _kept_var_idx(lowered)
            if kept is not None and len(kept) == n_main:
                # map the pruned lowering's numbering back onto the
                # traced args (jit drops unused args by default — the
                # two index spaces diverge whenever one exists)
                aliased = tuple(
                    sorted(kept[i] for i in low_aliased if i < len(kept))
                )
                alias_checked = True
            elif n_main == n_flat:
                aliased = tuple(sorted(low_aliased))  # nothing pruned
                alias_checked = True
            # else: pruned lowering with no kept-index map — don't
            # guess; alias facts stay unchecked rather than wrong
    return ProgramFingerprint(
        name=name,
        path=path,
        eqns=eqns,
        donated=donated,
        aliased=aliased,
        arg_labels=labels,
        mesh_axes=tuple(mesh_axes),
        world=world,
        alias_checked=alias_checked,
    )


def fingerprint_program(
    name: str,
    jitted,
    args: Sequence[Any],
    kwargs: Optional[Dict[str, Any]] = None,
    **meta,
) -> ProgramFingerprint:
    """Fingerprint a jitted callable at concrete example args. Prefers
    the AOT `trace` stage (donation + aliasing facts); falls back to
    `jax.make_jaxpr` on jax versions without it (collective sequence
    only, alias_checked=False)."""
    kwargs = kwargs or {}
    if hasattr(jitted, "trace"):
        return fingerprint_traced(name, jitted.trace(*args, **kwargs), **meta)
    import jax

    closed = jax.make_jaxpr(jitted)(*args, **kwargs)
    meta.setdefault("path", "")
    return ProgramFingerprint(
        name=name,
        eqns=tuple(collect_collectives(closed)),
        alias_checked=False,
        mesh_axes=tuple(meta.pop("mesh_axes", ())),
        world=meta.pop("world", None),
        path=meta.pop("path"),
    )


# ---------------------------------------------------------------------------
# rules J001-J004 (J005 is the runtime agreement in schedule.py)
# ---------------------------------------------------------------------------


def _finding_fingerprint(program: str, rule: str, detail: str) -> str:
    return hashlib.sha256(
        f"{program}|{rule}|{detail}".encode()
    ).hexdigest()[:16]


def expected_perms_from_plan(plan) -> Tuple[Tuple[Tuple[int, int], ...], ...]:
    """Per-round canonical ppermute pairs of a `plan.schedules.Plan`
    artifact: each round's send steps as sorted (src, dst) pairs. The
    J002 consistency reference — a driver body whose ppermute sequence
    no longer matches the registered artifact's rounds is flagged."""
    rounds = []
    for rnd in plan.rounds:
        pairs = set()
        for r, steps in enumerate(rnd.steps):
            for s in steps:
                if s.kind == "send":
                    pairs.add((int(r), int(s.peer)))
        if pairs:
            rounds.append(tuple(sorted(pairs)))
    return tuple(rounds)


def check_fingerprint(
    fp: ProgramFingerprint,
    *,
    registry_axes: frozenset = frozenset(),
    quantized_wire: bool = False,
    expected_perms: Optional[Sequence] = None,
    suppress: Sequence[Tuple[str, str]] = (),
    severity: Optional[Dict[str, str]] = None,
) -> List[Finding]:
    """Run J001-J004 over one program fingerprint. ``suppress`` is a
    sequence of (rule, reason) pairs from the program's registry entry —
    a reasoned suppression marks the finding suppressed (reported with
    --show-suppressed, never fails the gate)."""
    severity = severity or {}
    suppressed_rules = {r for r, _ in suppress}
    findings: List[Finding] = []
    path = fp.path or f"<program:{fp.name}>"

    def emit(rule: str, message: str, detail: str) -> None:
        sev = severity.get(rule, "error")
        if sev == "off":
            return
        findings.append(
            Finding(
                path=path,
                line=1,
                col=1,
                rule=rule,
                message=f"program {fp.name!r}: {message}",
                severity=sev,
                suppressed=rule in suppressed_rules,
                fingerprint=_finding_fingerprint(fp.name, rule, detail),
            )
        )

    # J001 — axis names must come from somewhere real
    known = set(fp.mesh_axes) | set(registry_axes)
    for eq in fp.eqns:
        for ax in eq.axes:
            if ax not in known:
                emit(
                    "J001",
                    f"collective eqn #{eq.index + 1} "
                    f"({eq.primitive}) binds axis {ax!r}, which is "
                    f"neither in the program's mesh {list(fp.mesh_axes)} "
                    "nor in the project-wide mesh-axis registry "
                    "(distlint R015 harvest)",
                    f"{eq.descriptor()}|{ax}",
                )

    # J002 — ppermute structural validity + plan-artifact consistency
    permutes = [e for e in fp.eqns if e.primitive == "ppermute"]
    size = fp.world
    for eq in permutes:
        perm = eq.perm or ()
        srcs = [a for a, _ in perm]
        dsts = [b for _, b in perm]
        problems = []
        if not perm:
            problems.append("empty permutation")
        if len(set(srcs)) != len(srcs):
            problems.append("duplicate sources")
        if len(set(dsts)) != len(dsts):
            problems.append("duplicate destinations")
        if size is not None and any(
            v < 0 or v >= size for v in srcs + dsts
        ):
            problems.append(f"endpoint outside world {size}")
        elif size is None and any(v < 0 for v in srcs + dsts):
            problems.append("negative endpoint")
        if problems:
            emit(
                "J002",
                f"collective eqn #{eq.index + 1} ppermute permutation "
                f"{list(eq.perm or ())} is invalid: "
                + ", ".join(problems),
                f"{eq.descriptor()}|invalid",
            )
    if expected_perms is not None:
        actual = [
            tuple(sorted(e.perm or ())) for e in permutes
        ]
        want = [tuple(sorted(p)) for p in expected_perms]
        if actual != want:
            k = next(
                (
                    i
                    for i, (a, b) in enumerate(zip(actual, want))
                    if a != b
                ),
                min(len(actual), len(want)),
            )
            emit(
                "J002",
                f"ppermute sequence diverges from the registered plan "
                f"artifact at round {k + 1}: program has "
                f"{actual[k] if k < len(actual) else '<none>'}, artifact "
                f"expects {want[k] if k < len(want) else '<none>'} "
                f"({len(actual)} ppermute eqn(s) vs {len(want)} "
                "artifact round(s))",
                f"artifact|{k}|{actual}|{want}",
            )

    # J003 — every donated leaf must actually alias in the lowering
    if fp.alias_checked:
        missing = sorted(set(fp.donated) - set(fp.aliased))
        for i in missing:
            label = (
                fp.arg_labels[i]
                if i < len(fp.arg_labels)
                else f"flat arg {i}"
            )
            emit(
                "J003",
                f"donated argument {label} (flat arg {i}) is NOT "
                "aliased in the lowered program — the donation was "
                "silently dropped, so the buffer round-trips a copy "
                "every step (the PR 4 306 ms/step memcpy class)",
                f"donate|{i}|{label}",
            )

    # J004 — quantized wire discipline
    if quantized_wire:
        for eq, (dt, shape), nbytes in quantized_wire_violations(fp.eqns):
            emit(
                "J004",
                f"collective eqn #{eq.index + 1} ({eq.primitive}) "
                f"carries a {dt} payload of shape {list(shape)} "
                f"({nbytes} bytes) on a wire-quantized path — payloads "
                "must be 1-byte dtypes (scale sidecars are exempt by "
                "the payload-size test)",
                f"{eq.descriptor()}|{dt}|{shape}",
            )

    return findings


# ---------------------------------------------------------------------------
# runtime registry + register-on-compile instrumentation (J005)
# ---------------------------------------------------------------------------


def armed() -> bool:
    """True when TDX_PROGLINT=1: compile seams register their programs
    and each registration runs the cross-rank agreement."""
    return os.environ.get(_ENV, "0") == "1"


class ProgramRegistry:
    """Process-global record of fingerprinted compiled programs."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: List[Tuple[str, int, ProgramFingerprint]] = []
        self._counts: Dict[str, int] = {}

    def record(self, fp: ProgramFingerprint) -> Tuple[int, int]:
        """Record; returns (global registration sequence, per-name
        ordinal). The GLOBAL sequence keys the J005 agreement round — in
        SPMD every rank registers programs in the same order, so rank A
        compiling a DIFFERENT program at sequence k than rank B is
        itself a divergence the agreement names immediately (keying by
        name would make skewed ranks wait on keys that never appear and
        fail by timeout instead of by diagnosis)."""
        with self._lock:
            seq = len(self._entries)
            k = self._counts.get(fp.name, 0)
            self._counts[fp.name] = k + 1
            self._entries.append((fp.name, k, fp))
            return seq, k

    def entries(self) -> List[Tuple[str, int, ProgramFingerprint]]:
        with self._lock:
            return list(self._entries)

    def get(self, name: str) -> List[ProgramFingerprint]:
        with self._lock:
            return [fp for n, _, fp in self._entries if n == name]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._counts.clear()


_registry = ProgramRegistry()


def registry() -> ProgramRegistry:
    """The process-global registry of the CANONICAL module instance.
    When this file runs as __main__ (`python -m ...tools.proglint`) it
    exists twice — the __main__ copy and the instance the compile seams
    import via `from ..tools import proglint` — and each copy has its
    own globals. The seams always record into the canonical import, so
    every reader resolves through it too."""
    import importlib

    return importlib.import_module(f"{_PKG}.tools.proglint")._registry


def _maybe_agree(fp: ProgramFingerprint, seq: int) -> None:
    """J005: publish + verify through the default group's incarnation-
    scoped store. Driver (single-controller) mode and uninitialized
    worlds agree structurally — one process compiles every rank's
    program from one schedule."""
    from .. import distributed as dist
    from .. import schedule as _schedule
    from ..store import PrefixStore

    w = dist._world
    pg = w.default_pg
    if (
        w.mode != "multiproc"
        or pg is None
        or pg.store is None
        or pg.size() <= 1
    ):
        return
    _schedule.agree_program(
        PrefixStore("proglint", pg.store),
        pg.rank(),
        pg.size(),
        f"reg{seq}",
        fp.canonical(),
    )


def register_fingerprint(fp: ProgramFingerprint, agree: bool = True) -> int:
    """Record a fingerprint in the process registry and (multiproc) run
    the J005 agreement — raises ProgramScheduleMismatchError on
    divergence, BEFORE the program's first dispatch."""
    seq, ordinal = registry().record(fp)
    if agree:
        _maybe_agree(fp, seq)
    return ordinal


def instrument(
    name: str,
    jitted,
    *,
    path: str = "",
    mesh_axes: Sequence[str] = (),
    world: Optional[int] = None,
):
    """The register-on-compile hook: wrap a jitted program so its FIRST
    call traces, fingerprints, registers and (multiproc) agrees before
    dispatching. Returns ``jitted`` unchanged when TDX_PROGLINT is off —
    the seams pay one env read and nothing else."""
    if not armed():
        return jitted
    lock = threading.Lock()
    done: List[bool] = []

    def wrapper(*args, **kwargs):
        # registration is a HOST effect (trace + lower + a blocking
        # store agreement) — exactly the class R011/TraceGuard police.
        # An instrumented program can itself be called from inside an
        # enclosing jit trace (a caller that re-wraps the ddp step's
        # programs); registering there would block the trace, so defer
        # to the first EAGER call instead of firing mid-trace.
        if not done and not traceguard.under_tracing():
            with lock:
                if not done:
                    fp = fingerprint_program(
                        name,
                        jitted,
                        args,
                        kwargs,
                        path=path,
                        mesh_axes=mesh_axes,
                        world=world,
                    )
                    register_fingerprint(fp)
                    done.append(True)
        return jitted(*args, **kwargs)

    wrapper.__name__ = getattr(jitted, "__name__", name)
    # NOT __wrapped__: jax.jit itself sets that on its returned callable
    # (pointing at the undecorated python fn), so `_unwrap` keys on a
    # proglint-specific attribute to strip exactly one layer — ours
    wrapper._proglint_wrapped = jitted
    return wrapper


# ---------------------------------------------------------------------------
# the repo's registered program catalog (CLI / self-gate / corpus)
# ---------------------------------------------------------------------------

_PKG = "pytorch_distributed_example_tpu"


@dataclass(frozen=True)
class ProgramMeta:
    """Per-program rule knobs carried by the catalog."""

    quantized_wire: bool = False
    expected_perms: Optional[Tuple] = None
    suppress: Tuple[Tuple[str, str], ...] = ()


def _unwrap(fn):
    return getattr(fn, "_proglint_wrapped", fn)


def _tiny_model():
    import jax
    import jax.numpy as jnp

    from ..models import TransformerConfig, TransformerLM

    cfg = TransformerConfig(
        vocab_size=32,
        d_model=16,
        n_layers=1,
        n_heads=2,
        max_seq_len=16,
        use_flash=False,
    )
    model = TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    return model, params


def _serve_programs() -> List[Tuple[ProgramFingerprint, ProgramMeta]]:
    import jax
    import jax.numpy as jnp

    from ..serve import decode as _decode
    from ..serve.cache import PagedKVCache

    model, variables = _tiny_model()
    params = variables["params"]
    path = f"{_PKG}/serve/decode.py"
    S = 2
    out: List[Tuple[ProgramFingerprint, ProgramMeta]] = []

    lengths = jnp.zeros((S,), jnp.int32)
    tokens = jnp.zeros((S,), jnp.int32)
    rngs = jnp.zeros((S, 2), jnp.uint32)
    key = jnp.zeros((2,), jnp.uint32)

    pool = PagedKVCache(model, slots=S, num_blocks=8, block_size=4)
    nb = pool.block_tables.shape[1]
    pc, ft, at, st = map(_unwrap, _decode.paged_programs(model, 0.0, None))
    bt = jnp.zeros((S, nb), jnp.int32)
    chunk = jnp.zeros((1, 8), jnp.int32)
    logits = jnp.zeros((8, model.cfg.vocab_size), jnp.float32)
    for name, fn, args in (
        (
            "serve.paged.prefill_chunk",
            pc,
            (params, pool.tree, chunk, bt[:1], 0),
        ),
        ("serve.paged.first_token", ft, (logits, 7, 0)),
        (
            "serve.paged.attach",
            at,
            (lengths, tokens, rngs, 0, 8, jnp.int32(0), key),
        ),
        (
            "serve.paged.step",
            st,
            (params, pool.tree, lengths, tokens, rngs, bt),
        ),
    ):
        out.append(
            (
                fingerprint_program(name, fn, args, path=path),
                ProgramMeta(),
            )
        )
    return out


@contextlib.contextmanager
def _armed_env():
    prev = os.environ.get(_ENV)
    os.environ[_ENV] = "1"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(_ENV, None)
        else:
            os.environ[_ENV] = prev


def _ddp_programs(group) -> List[Tuple[ProgramFingerprint, ProgramMeta]]:
    """Fingerprint the DDP trainer's compiled steps by driving ONE tiny
    step through the real factory with the registry armed — the ZeRO
    program only exists after first dispatch (its spec tree needs a
    concrete optimizer state), and going through the seam also proves
    the register-on-compile hook end to end."""
    import numpy as np
    import optax

    from ..parallel.ddp import make_ddp_train_step

    W = group.size()

    def apply_fn(params, x):
        return x @ params["w"] + params["b"]

    def loss_fn(logits, y):
        return ((logits - y) ** 2).mean()

    optimizer = optax.adam(1e-3)
    params = {
        "w": np.zeros((4, 3), np.float32),
        "b": np.zeros((3,), np.float32),
    }
    x = np.zeros((2 * W, 4), np.float32)
    y = np.zeros((2 * W, 3), np.float32)
    out = []
    with _armed_env():
        for mode in ("auto", "off"):
            before = {id(fp) for _, _, fp in registry().entries()}
            step = make_ddp_train_step(
                apply_fn,
                loss_fn,
                optimizer,
                group=group,
                shard_weight_update=mode,
            )
            opt_state = (
                step.init_opt_state(params)
                if mode == "auto" and hasattr(step, "init_opt_state")
                else optimizer.init(params)
            )
            step(params, opt_state, x, y)
            fresh = [
                (name, fp)
                for name, _, fp in registry().entries()
                if id(fp) not in before and name.startswith("ddp.")
            ]
            for _, fp in fresh:
                out.append((fp, ProgramMeta()))
    return out


def _plan_programs(group) -> List[Tuple[ProgramFingerprint, ProgramMeta]]:
    import numpy as np

    from ..backends.xla import AXIS
    from ..plan import driver as plan_driver
    from ..plan import schedules, topology

    W = group.size()
    mesh = group.mesh.jax_mesh
    path = f"{_PKG}/plan/driver.py"
    topo = topology.Topology(W, (tuple(range(W)),), "cpu")
    n = 8
    out = []
    cases = (
        ("all_reduce", "ring", (W, n)),
        ("all_reduce", "rhd", (W, n)),
        ("all_gather", "ring", (W, n)),
        ("reduce_scatter", "ring", (W, W, n)),
    )
    for op_name, alg, shape in cases:
        prog = _unwrap(
            plan_driver.compiled_body(op_name, alg, W, AXIS, mesh, "sum")
        )
        x = np.zeros(shape, np.float32)
        if alg == "rhd" or op_name in ("all_gather", "reduce_scatter"):
            plan = schedules.synthesize(op_name, alg, W, n, topo)
            expected = expected_perms_from_plan(plan)
        else:
            expected = ()  # driver ring all_reduce: no ppermutes at all
        fp = fingerprint_program(
            f"plan.{op_name}.{alg}",
            prog,
            (x,),
            path=path,
            mesh_axes=tuple(mesh.axis_names),
            world=W,
        )
        out.append(
            (fp, ProgramMeta(expected_perms=tuple(expected)))
        )
    return out


def _traced_programs(group) -> List[Tuple[ProgramFingerprint, ProgramMeta]]:
    """Fingerprint the trace-time planner dispatch seam
    (`plan/traced.py`) with a seeded schedule table — the lowered
    bodies TP/FSDP/ZeRO call sites emit once `prepare()` has agreed a
    non-stock schedule.  Each registered artifact's `expected_perms`
    pins the J002 consistency contract: the traced lowering's ppermute
    sequence must match the plan the agreement round published."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from .._compat import shard_map_fn
    from ..backends.xla import AXIS
    from ..plan import driver as plan_driver
    from ..plan import schedules, topology, traced

    W = group.size()
    mesh = group.mesh.jax_mesh
    path = f"{_PKG}/plan/traced.py"
    topo = topology.Topology(W, (tuple(range(W)),), "cpu")
    n, m, k, p = 8, 2, 4, 3
    ring_rounds = tuple(
        tuple(sorted((i, (i + 1) % W) for i in range(W)))
        for _ in range(W - 1)
    )

    def _expected(op, alg):
        if op == "all_reduce" and alg == "ring":
            return ()  # psum_scatter + all_gather body: no ppermutes
        return expected_perms_from_plan(
            schedules.synthesize(op, alg, W, n, topo)
        )

    cases = [
        (
            "all_reduce", alg,
            lambda t: traced.all_reduce(t, AXIS, reduce_kind="sum"),  # distlint: disable=R004 -- seeded-table catalog body: axis routes it, no group dispatch under test
            np.zeros((W, n), np.float32), P(AXIS), _expected("all_reduce", alg),
        )
        for alg in ("ring", "rhd")
        if plan_driver.supports("all_reduce", alg, W, "sum")
    ]
    cases.append((
        "all_gather", "ring",
        lambda t: traced.all_gather(t[0], AXIS, dim=0, tiled=True)[None],  # distlint: disable=R004 -- seeded-table catalog body: axis routes it, no group dispatch under test
        np.zeros((W, n), np.float32), P(AXIS), _expected("all_gather", "ring"),
    ))
    cases.append((
        "reduce_scatter", "ring",
        lambda t: traced.reduce_scatter(t[0], AXIS, reduce_kind="sum")[None],  # distlint: disable=R004 -- seeded-table catalog body: axis routes it, no group dispatch under test
        np.zeros((W, W * n), np.float32), P(AXIS),
        _expected("reduce_scatter", "ring"),
    ))

    env_keys = ("TDX_COLLECTIVE_PLANNER", "TDX_PLANNER_FORCE",
                "TDX_PLANNER_OVERLAP")
    saved_env = {key: os.environ.get(key) for key in env_keys}
    out = []
    try:
        # pin the dispatch ladder to the seeded table: planner env off
        # (no force/planner fallbacks), overlap on (decomposed gathers)
        os.environ["TDX_COLLECTIVE_PLANNER"] = "0"
        os.environ.pop("TDX_PLANNER_FORCE", None)
        os.environ["TDX_PLANNER_OVERLAP"] = "1"
        for op_name, alg, body, x, spec, expected in cases:
            traced.reset()
            traced.seed(
                op_name, alg, world=W,
                nbytes=(x.size // W) * x.dtype.itemsize,
                source="proglint",
            )
            prog = jax.jit(shard_map_fn(
                body, mesh=mesh, in_specs=spec, out_specs=P(AXIS)
            ))
            fp = fingerprint_program(
                f"plan.traced.{op_name}.{alg}",
                prog,
                (x,),
                path=path,
                mesh_axes=tuple(mesh.axis_names),
                world=W,
            )
            out.append((fp, ProgramMeta(expected_perms=tuple(expected))))

        # the overlapped collective-matmul: its own ppermute loop (one
        # ring hop per round, own chunk's matmul issued first)
        traced.reset()
        xg = np.zeros((W, m, k), np.float32)
        wmat = np.zeros((k, p), np.float32)
        traced.seed(
            "all_gather", "ring", world=W,
            nbytes=m * k * 4, source="proglint",
        )
        prog = jax.jit(shard_map_fn(
            lambda t, wm: traced.all_gather_matmul(t[0], wm, AXIS)[None],  # distlint: disable=R004 -- seeded-table catalog body: axis routes it, no group dispatch under test
            mesh=mesh, in_specs=(P(AXIS), P()), out_specs=P(AXIS),
        ))
        fp = fingerprint_program(
            "plan.traced.all_gather_matmul.ring",
            prog,
            (xg, wmat),
            path=path,
            mesh_axes=tuple(mesh.axis_names),
            world=W,
        )
        out.append((fp, ProgramMeta(expected_perms=ring_rounds)))
    finally:
        traced.reset()
        for key, val in saved_env.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
    return out


def _quant_programs(group) -> List[Tuple[ProgramFingerprint, ProgramMeta]]:
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from .._compat import shard_map_fn
    from ..backends.xla import AXIS
    from ..ops.quant import quantized_all_reduce

    W = group.size()
    mesh = group.mesh.jax_mesh
    fn = jax.jit(
        shard_map_fn(
            lambda t: quantized_all_reduce(t, AXIS),
            mesh=mesh,
            in_specs=P(AXIS),
            out_specs=P(AXIS),
        )
    )
    x = np.zeros((W, 512), np.float32)
    fp = fingerprint_program(
        "ops.quantized_all_reduce",
        fn,
        (x,),
        path=f"{_PKG}/ops/quant.py",
        mesh_axes=tuple(mesh.axis_names),
        world=W,
    )
    return [(fp, ProgramMeta(quantized_wire=True))]


def build_repo_programs() -> List[Tuple[ProgramFingerprint, ProgramMeta]]:
    """Trace + fingerprint every registered repo compiled program on the
    current devices (tiny shapes; trace-only except the DDP steps, which
    execute one step on a 4x3 linear model to materialize the ZeRO
    path). Needs >= 2 devices and an initialized (driver-mode) default
    process group — `main()` arranges both."""
    import jax

    from .. import distributed as dist

    if len(jax.devices()) < 2:
        raise RuntimeError(
            "proglint: needs >= 2 devices to trace the repo's collective "
            "programs (force a virtual CPU mesh, e.g. "
            "_compat.force_cpu_devices(2))"
        )
    if not dist.is_initialized():
        dist.init_process_group(backend="xla")
    group = dist._get_default_group()
    out: List[Tuple[ProgramFingerprint, ProgramMeta]] = []
    out.extend(_serve_programs())
    out.extend(_ddp_programs(group))
    out.extend(_plan_programs(group))
    out.extend(_traced_programs(group))
    out.extend(_quant_programs(group))
    return out


# ---------------------------------------------------------------------------
# config + lint entry + corpus
# ---------------------------------------------------------------------------


@dataclass
class ProglintConfig:
    severity: Dict[str, str] = field(default_factory=dict)
    corpus: str = "tests/fixtures/proglint"


def load_config(root: str = ".") -> ProglintConfig:
    """``[tool.proglint]`` from pyproject.toml (missing → defaults)."""
    cfg = ProglintConfig()
    pp = os.path.join(root, "pyproject.toml")
    if not os.path.isfile(pp):
        return cfg
    try:
        try:
            import tomllib
        except ImportError:  # py310
            import tomli as tomllib
        with open(pp, "rb") as f:
            doc = tomllib.load(f)
    except Exception as e:
        raise ValueError(f"could not parse {pp}: {e}") from e
    section = doc.get("tool", {}).get("proglint", {})
    if "corpus" in section:
        cfg.corpus = str(section["corpus"])
    for rule, sev in dict(section.get("severity", {})).items():
        sev = str(sev).lower()
        if sev not in SEVERITIES:
            raise ValueError(
                f"[tool.proglint.severity] {rule} = {sev!r}: must be one "
                f"of {SEVERITIES}"
            )
        cfg.severity[str(rule).upper()] = sev
    return cfg


def lint_repo_programs(
    root: str = ".",
    pairs: Optional[
        List[Tuple[ProgramFingerprint, ProgramMeta]]
    ] = None,
    config: Optional[ProglintConfig] = None,
) -> List[Finding]:
    """J001-J004 over the repo's registered programs, with J001 fed by
    distlint's harvested mesh-axis registry (one source of truth)."""
    config = config or load_config(root)
    axes = harvested_mesh_axes(root)
    if pairs is None:
        pairs = build_repo_programs()
    findings: List[Finding] = []
    for fp, meta in pairs:
        findings.extend(
            check_fingerprint(
                fp,
                registry_axes=axes,
                quantized_wire=meta.quantized_wire,
                expected_perms=meta.expected_perms,
                suppress=meta.suppress,
                severity=config.severity,
            )
        )
    return findings


def corpus_diff(
    pairs: List[Tuple[ProgramFingerprint, ProgramMeta]],
    corpus_dir: str,
    names: Optional[Sequence[str]] = None,
) -> List[str]:
    """Drift report between live fingerprints and the golden corpus:
    one line per divergence (missing file, changed collective sequence,
    changed donation set). Empty list == no drift."""
    problems: List[str] = []
    wanted = set(names) if names is not None else None
    for fp, _ in pairs:
        if wanted is not None and fp.name not in wanted:
            continue
        fn = os.path.join(corpus_dir, fp.name.replace("/", "_") + ".json")
        if not os.path.isfile(fn):
            problems.append(
                f"{fp.name}: no golden corpus entry at {fn} "
                "(run --update-corpus)"
            )
            continue
        with open(fn, "r", encoding="utf-8") as fh:
            want = json.load(fh)
        have = fp.canonical()
        if have == want:
            continue
        mine = [
            f"{fp.name}: {key} drifted from the golden corpus — "
            f"have {have.get(key)!r}, corpus {want.get(key)!r}"
            for key in ("eqns", "donated", "aliased", "mesh_axes", "world")
            if have.get(key) != want.get(key)
        ]
        if not mine and have["digest"] != want.get("digest"):
            # per-field lists match but the recorded digest does not
            # (hand-edited/tampered corpus entry)
            mine = [
                f"{fp.name}: digest drifted "
                f"({want.get('digest')} -> {have['digest']})"
            ]
        problems.extend(mine)
    return problems


def write_corpus(
    pairs: List[Tuple[ProgramFingerprint, ProgramMeta]],
    corpus_dir: str,
    names: Optional[Sequence[str]] = None,
) -> int:
    os.makedirs(corpus_dir, exist_ok=True)
    wanted = set(names) if names is not None else None
    n = 0
    for fp, _ in pairs:
        if wanted is not None and fp.name not in wanted:
            continue
        fn = os.path.join(corpus_dir, fp.name.replace("/", "_") + ".json")
        with open(fn, "w", encoding="utf-8") as fh:
            json.dump(fp.canonical(), fh, indent=1, sort_keys=True)
            fh.write("\n")
        n += 1
    return n


# Golden-corpus membership (the drift gate in tier-1): the paged decode
# step, the ZeRO train step, and the ppermute-carrying planner bodies.
CORPUS_PROGRAMS = (
    "serve.paged.step",
    "ddp.train_step.zero",
    "plan.all_reduce.ring",
    "plan.all_reduce.rhd",
    "plan.all_gather.ring",
    "plan.reduce_scatter.ring",
)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


_INFO_URI = f"{_PKG}/tools/proglint.py"


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="proglint",
        description=(
            "jaxpr-level program-plane analyzer (rules J001-J005) over "
            "the repo's registered compiled programs"
        ),
    )
    ap.add_argument("--root", default=".", help="repo root")
    ap.add_argument(
        "--format", choices=("human", "json", "sarif"), default="human"
    )
    ap.add_argument("--baseline", help="baseline file (ratchet)")
    ap.add_argument("--update-baseline", action="store_true")
    ap.add_argument("--force-baseline-growth", action="store_true")
    ap.add_argument("--show-suppressed", action="store_true")
    ap.add_argument(
        "--list", action="store_true",
        help="list registered programs + fingerprints, run no rules",
    )
    ap.add_argument(
        "--corpus", action="store_true",
        help="also gate the golden corpus (config [tool.proglint] corpus)",
    )
    ap.add_argument(
        "--update-corpus", action="store_true",
        help="rewrite the golden corpus from the live fingerprints",
    )
    args = ap.parse_args(argv)
    if args.update_baseline and not args.baseline:
        print(
            "proglint: --update-baseline requires --baseline FILE",
            file=sys.stderr,
        )
        return 2

    # a lint CLI must never grab an accelerator; the repo programs need
    # a >=2-device geometry, so force a 2-device virtual CPU mesh before
    # the first jax backend touch (a no-op if the backend already
    # materialized — build_repo_programs re-checks the device count)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from .._compat import force_cpu_devices

    try:
        force_cpu_devices(2)
    except RuntimeError:
        pass  # backend already initialized by the embedding process

    try:
        config = load_config(args.root)
    except ValueError as e:
        print(f"proglint: {e}", file=sys.stderr)
        return 2
    pairs = build_repo_programs()

    if args.list:
        for fp, meta in pairs:
            tags = []
            if meta.quantized_wire:
                tags.append("quantized-wire")
            if meta.expected_perms is not None:
                tags.append("plan-artifact")
            print(
                f"{fp.name}  digest={fp.digest}  "
                f"eqns={len(fp.eqns)} donated={len(fp.donated)} "
                f"aliased={len(fp.aliased)}"
                + (f"  [{', '.join(tags)}]" if tags else "")
            )
        return 0

    findings = lint_repo_programs(args.root, pairs, config)

    corpus_problems: List[str] = []
    corpus_dir = os.path.join(args.root, config.corpus)
    if args.update_corpus:
        n = write_corpus(pairs, corpus_dir, CORPUS_PROGRAMS)
        print(
            f"proglint: corpus updated ({n} programs)", file=sys.stderr
        )
    elif args.corpus:
        corpus_problems = corpus_diff(pairs, corpus_dir, CORPUS_PROGRAMS)

    stale_entries: List[Dict] = []
    if args.baseline:
        try:
            baseline = load_baseline(args.baseline)
        except FileNotFoundError:
            baseline = {"findings": []}
        except (OSError, ValueError) as e:
            print(f"proglint: {e}", file=sys.stderr)
            return 2
        _, _, stale_entries = apply_baseline(findings, baseline)
        if args.update_baseline:
            try:
                n = write_baseline(
                    args.baseline,
                    findings,
                    allow_growth=args.force_baseline_growth,
                    tool="proglint",
                )
            except ValueError as e:
                print(f"proglint: {e}", file=sys.stderr)
                return 2
            print(
                f"proglint: baseline updated ({n} entries)",
                file=sys.stderr,
            )

    if args.format == "json":
        print(json.dumps([f.to_dict() for f in findings], indent=2))
    elif args.format == "sarif":
        print(
            json.dumps(
                render_sarif(
                    findings,
                    args.show_suppressed,
                    baseline_mode=bool(args.baseline),
                    tool_name="proglint",
                    rules=RULES,
                    information_uri=_INFO_URI,
                    fingerprint_key="proglint/v1",
                ),
                indent=2,
            )
        )
    else:
        print(
            render_report(findings, args.show_suppressed, tool="proglint")
        )
    for p in corpus_problems:
        print(f"proglint: corpus drift: {p}", file=sys.stderr)
    if stale_entries:
        print(
            f"proglint: {len(stale_entries)} stale baseline entr"
            f"{'y' if len(stale_entries) == 1 else 'ies'} — run "
            "--update-baseline to shrink the ratchet",
            file=sys.stderr,
        )
    active = [
        f
        for f in findings
        if not f.suppressed and not f.baselined and f.severity == "error"
    ]
    return 1 if (active or corpus_problems) else 0


if __name__ == "__main__":
    sys.exit(main())
