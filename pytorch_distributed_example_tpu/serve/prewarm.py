"""Geometry pre-warm — pay resize compile cost BEFORE the resize.

The dominant cost of a process-level resize is not the drain, the
seal, or the re-register (all milliseconds against the store): it is
the NEW generation's engines compiling their programs from scratch
(seconds, even for small models). But the autoscaler's reachable set
is tiny by construction — hysteresis bands plus the max-step clamp
bound the worlds it can ever request to ``[min_replicas,
max_replicas]`` (a handful), and the engine's bucketed shapes bound
the programs per world — so every program a resize could need is
enumerable AHEAD of time.

Two layers make that cheap:

* `enable_compile_cache` points JAX's persistent compilation cache at
  a directory shared by every worker incarnation (workers name one via
  ``TDX_COMPILE_CACHE``; a machine-level `JAX_COMPILATION_CACHE_DIR`
  takes precedence). The cache is keyed by HLO + flags + backend,
  so a program compiled by ANY process (a pre-warm pass, a previous
  generation, a sibling rank) is a disk read for the next one.
* `prewarm_engine_programs` AOT-compiles the engine's paged program
  quadruple (`serve/decode.py`) for every prefill bucket via
  ``jit.lower(args).compile()`` — lowering with the engine's own
  params/pool/lane arrays traces WITHOUT executing (donation included:
  nothing is consumed), and compiling populates the persistent cache
  with byte-identical HLO to what the serving loop will request.

The persistent cache alone is not "milliseconds": it skips XLA
compilation but a respawned worker still re-TRACES every program
(python+flax time that dominates on small models). The third layer
closes that too: `prewarm_engine_programs(save_dir=...)` serializes
the compiled executables themselves (`jax.experimental.
serialize_executable`), and `load_precompiled` + the engine's
``precompiled=`` knob attach them to a fresh engine with shape-guarded
dispatch — matching calls run the deserialized executable directly
(no trace, no compile), anything else falls back to the jit path
unchanged. Deserializing the whole quadruple is ~10x cheaper than
retracing it even on the tiny CI model.

Data-parallel width does NOT multiply the program set: every DP
replica runs the SAME single-chip programs, so one warmed cache entry
serves all worlds in the autoscaler's band — `reachable_geometries`
returns the (world, tp, bucket) tuples for planning/reporting, and
the warm pass dedups them down to the distinct (tp, bucket) programs.
"""

from __future__ import annotations

import json
import os
import pickle
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = [
    "GeometrySpec",
    "enable_compile_cache",
    "reachable_geometries",
    "prewarm_engine_programs",
    "load_precompiled",
    "attach_precompiled",
]

_MANIFEST = "prewarm-manifest.json"


def _engine_tp(engine) -> int:
    """The TP degree an engine's programs were traced under — the
    mesh's ``tp`` axis extent, 1 for unmeshed engines. Keys the
    per-degree namespace inside a shared pre-warm dir: a disaggregated
    deployment warms one dir for BOTH pools' degrees (prefill TP !=
    decode TP) and each engine loads only its own shapes."""
    mesh = getattr(engine, "mesh", None)
    if mesh is None:
        return 1
    jmesh = getattr(mesh, "jax_mesh", mesh)
    try:
        return int(dict(jmesh.shape).get("tp", 1))
    except Exception:
        return 1


@dataclass(frozen=True, order=True)
class GeometrySpec:
    """One geometry the autoscaler can land the gang on: `world` DP
    replicas, each a `tp`-way engine serving prefill bucket `bucket`."""

    world: int
    tp: int
    bucket: int


def enable_compile_cache(cache_dir: str, min_compile_secs: float = 0.0):
    """Share one persistent compilation cache across worker incarnations
    (the resize fast path) and return its directory: ``cache_dir``,
    unless the machine pins `JAX_COMPILATION_CACHE_DIR`, which wins
    (`_compat.enable_compile_cache` holds the rule). Zero threshold on
    purpose: the serve programs are small on test models but their
    re-compile is exactly the latency a resize pays, so EVERYTHING the
    engine compiles is worth the disk here (the bounded program set
    keeps the directory small)."""
    from .._compat import enable_compile_cache as _enable

    return _enable(cache_dir, min_compile_secs)


def reachable_geometries(
    policy,
    current_world: int,
    buckets: List[int],
    tp: int = 1,
    horizon: Optional[int] = None,
) -> List[GeometrySpec]:
    """Enumerate every (world, tp, bucket) the autoscaler can reach.

    `policy` is an `AutoscalePolicy` (min/max_replicas + max_step);
    `horizon` bounds how many DECISIONS ahead to plan — each decision
    moves at most `max_step` replicas, so ``horizon=1`` is the next
    tick's worlds only. None plans the whole hysteresis band."""
    lo = int(getattr(policy, "min_replicas", 1))
    hi = int(getattr(policy, "max_replicas", current_world))
    if horizon is not None:
        step = int(getattr(policy, "max_step", 1))
        lo = max(lo, int(current_world) - horizon * step)
        hi = min(hi, int(current_world) + horizon * step)
    return [
        GeometrySpec(world=w, tp=int(tp), bucket=int(b))
        for w in range(lo, hi + 1)
        for b in sorted(set(int(b) for b in buckets))
    ]


def prewarm_engine_programs(
    engine,
    cache_dir: Optional[str] = None,
    buckets: Optional[List[int]] = None,
    save_dir: Optional[str] = None,
) -> Dict[Tuple[str, int], float]:
    """AOT-compile the engine's paged quadruple for every prefill
    bucket, populating the (optionally enabled) persistent cache with
    exactly the HLO the serving loop will request — so a post-resize
    engine's first token costs a cache READ, not a compile. With
    `save_dir` the compiled executables are ALSO serialized to disk
    for `load_precompiled` — the resize fast path that skips even the
    re-trace.

    Lowers with the engine's OWN arrays (params, pool tree, lane
    vectors, block tables): real avals guarantee byte-identical traces
    to the live calls, and `.lower()` never executes — donated buffers
    survive untouched. Returns {(program, shape_key): seconds} — the
    runbook's compile-budget breakdown."""
    import jax

    if cache_dir is not None:
        enable_compile_cache(cache_dir)
    bt = engine.cache.block_tables
    S, _nb = bt.shape
    timings: Dict[Tuple[str, int], float] = {}
    compiled: Dict[Tuple[str, int], object] = {}
    # chunked prefill runs ONE program (the chunk length); unchunked
    # runs one per bucket — mirror the engine's dispatch exactly
    if engine.prefill_chunk_tokens is not None:
        chunk_lens = [int(engine.prefill_chunk_tokens)]
    else:
        chunk_lens = [
            int(b) for b in (buckets if buckets is not None else engine.buckets)
        ]
    first_aval = None
    for C in sorted(set(chunk_lens)):
        t0 = time.perf_counter()
        args = (
            engine.params,
            engine.cache.tree,
            np.zeros((1, C), np.int32),
            bt[:1],
            0,
        )
        compiled[("prefill_chunk", C)] = (
            engine._prefill_chunk.lower(*args).compile()
        )
        timings[("prefill_chunk", C)] = time.perf_counter() - t0
        if first_aval is None:
            # chain the logits aval into the sampler's warm pass
            _, first_aval = jax.eval_shape(engine._prefill_chunk, *args)
        t0 = time.perf_counter()
        logits = np.zeros((C,) + first_aval.shape[1:], first_aval.dtype)
        compiled[("first_token", C)] = (
            engine._first_token.lower(logits, C - 1, 0).compile()
        )
        timings[("first_token", C)] = time.perf_counter() - t0
    t0 = time.perf_counter()
    tok_aval, key_aval = jax.eval_shape(
        engine._first_token,
        np.zeros((1,) + first_aval.shape[1:], first_aval.dtype),
        0,
        0,
    )
    compiled[("attach", S)] = engine._attach.lower(
        engine._dev_lengths,
        engine._dev_tokens,
        engine._dev_rngs,
        0,
        1,
        np.zeros(tok_aval.shape, tok_aval.dtype),
        np.zeros(key_aval.shape, key_aval.dtype),
    ).compile()
    timings[("attach", S)] = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled[("step", S)] = engine._step.lower(
        engine.params,
        engine.cache.tree,
        engine._dev_lengths,
        engine._dev_tokens,
        engine._dev_rngs,
        bt,
    ).compile()
    timings[("step", S)] = time.perf_counter() - t0
    if save_dir is not None:
        _save_precompiled(compiled, save_dir, tp=_engine_tp(engine))
    return timings


def _save_precompiled(compiled: Dict, save_dir: str, tp: int = 1) -> None:
    """Serialize compiled executables + a manifest into `save_dir`,
    namespaced by TP degree. Same-host, same-jax-version artifacts
    (the deploy contract a worker fleet already satisfies);
    `load_precompiled` rejects anything it cannot deserialize rather
    than crashing a worker.

    The manifest MERGES: one pre-warm dir accumulates executables for
    MULTIPLE TP degrees (a disagg deployment warms prefill-TP and
    decode-TP passes into the same dir), each pass updating only its
    own ``{name}:{shape}:tp{tp}`` keys. The write stays atomic
    (tmp + replace), so a reader never sees a torn manifest — at worst
    it sees the pre-merge one and cold-compiles the new degree."""
    from jax.experimental import serialize_executable as se

    os.makedirs(save_dir, exist_ok=True)
    path = os.path.join(save_dir, _MANIFEST)
    try:
        with open(path) as f:
            manifest = json.load(f)
    except (OSError, ValueError):
        manifest = {}
    for (name, shape), exe in compiled.items():
        fname = f"{name}-{int(shape)}-tp{int(tp)}.exe"
        with open(os.path.join(save_dir, fname), "wb") as f:
            pickle.dump(se.serialize(exe), f)
        manifest[f"{name}:{int(shape)}:tp{int(tp)}"] = fname
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f)
    os.replace(tmp, path)


def _parse_manifest_key(key: str) -> Optional[Tuple[str, int, int]]:
    """``name:shape[:tpN]`` -> (name, shape, tp); legacy two-part keys
    (pre-disagg manifests) are tp=1. None for anything malformed."""
    parts = key.split(":")
    try:
        if len(parts) == 2:
            return parts[0], int(parts[1]), 1
        if len(parts) == 3 and parts[2].startswith("tp"):
            return parts[0], int(parts[1]), int(parts[2][2:])
    except ValueError:
        return None
    return None


def load_precompiled(
    save_dir: str, tp: Optional[int] = None, mesh=None
) -> Dict[Tuple[str, int], object]:
    """Deserialize a pre-warm pass's executables FOR ONE TP DEGREE —
    selected explicitly (``tp=``) or from the engine's mesh shape
    (``mesh=``; its ``tp`` axis extent, 1 when absent/None). A shared
    multi-degree dir thus hands each pool exactly the executables its
    geometry traced; legacy manifests without the tp suffix load as
    tp=1. Returns {} when the directory has no (complete) manifest and
    silently drops entries that fail to load — a worker with a stale
    or foreign pre-warm dir degrades to cold compiles, it never
    refuses to start."""
    from jax.experimental import serialize_executable as se

    if tp is None:
        if mesh is None:
            tp = 1
        else:
            jmesh = getattr(mesh, "jax_mesh", mesh)
            try:
                tp = int(dict(jmesh.shape).get("tp", 1))
            except Exception:
                tp = 1
    path = os.path.join(save_dir, _MANIFEST)
    try:
        with open(path) as f:
            manifest = json.load(f)
    except (OSError, ValueError):
        return {}
    out: Dict[Tuple[str, int], object] = {}
    for key, fname in manifest.items():
        parsed = _parse_manifest_key(key)
        if parsed is None or parsed[2] != int(tp):
            continue
        name, shape, _tp = parsed
        try:
            with open(os.path.join(save_dir, fname), "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
            out[(name, shape)] = se.deserialize_and_load(
                payload, in_tree, out_tree
            )
        except Exception:
            continue
    return out


class _ChunkDispatch:
    """Route a paged-program call to the pre-deserialized executable
    matching its dispatch width, falling back to the jit wrapper for
    anything unwarmed. Argument-mismatch errors (a pre-warm from a
    different model/pool geometry) raise BEFORE execution, so the
    fallback re-runs with every donated buffer intact."""

    def __init__(self, fallback, table: Dict[int, object], pick):
        self._fallback = fallback
        self._table = table
        self._pick = pick

    def __call__(self, *args):
        exe = self._table.get(self._pick(*args))
        if exe is None:
            return self._fallback(*args)
        try:
            return exe(*args)
        except (TypeError, ValueError):
            return self._fallback(*args)


def attach_precompiled(programs, precompiled: Dict, slots: int):
    """Overlay pre-warmed executables onto a `paged_programs`
    quadruple: per-chunk-width dispatch for prefill/first-token, a
    direct swap (same guarded fallback) for the slot-shaped attach and
    step programs. Returns the new quadruple."""
    prefill, first, attach, step = programs
    pre_tab = {
        shape: exe
        for (name, shape), exe in precompiled.items()
        if name == "prefill_chunk"
    }
    first_tab = {
        shape: exe
        for (name, shape), exe in precompiled.items()
        if name == "first_token"
    }
    if pre_tab:
        prefill = _ChunkDispatch(
            prefill, pre_tab, lambda *a: a[2].shape[1]
        )
    if first_tab:
        first = _ChunkDispatch(
            first, first_tab, lambda *a: a[0].shape[0]
        )
    if ("attach", slots) in precompiled:
        attach = _ChunkDispatch(
            attach,
            {slots: precompiled[("attach", slots)]},
            lambda *a: a[0].shape[0],
        )
    if ("step", slots) in precompiled:
        step = _ChunkDispatch(
            step,
            {slots: precompiled[("step", slots)]},
            lambda *a: a[2].shape[0],
        )
    return prefill, first, attach, step
