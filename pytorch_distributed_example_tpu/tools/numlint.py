"""numlint — numerics/determinism-plane analyzer + geometry parity
sweeper (ISSUE 18).

The five existing guard planes check *structure*: distlint proves the
SOURCE cannot diverge (R001-R015), proglint pins the compiled PROGRAM
(J001-J005), storelint the coordination KEY SPACE (S001-S007), the
ScheduleVerifier the executed schedule, TraceGuard the trace boundary.
None of them checks *values* — a dtype drift, an accumulation-order
change, or a reused PRNG key sails through all five until a parity
test happens to trip. numlint is the sixth plane: it enforces the
repo's NUMERICS CONTRACTS (`@numerics_contract` in numerics.py — the
bitwise ZeRO-update claim of PR 10, the token-exact serve claim of
PR 16, the tolerance envelopes of the PR 7/11 codecs).

Static half — rules over distlint's whole-project call graph, with
contract reachability propagated along call edges (a helper CALLED BY
a bitwise-contracted function is itself on a bitwise path):

  N001  matmul-family call without pinned `precision=` /
        `preferred_element_type=` on a bitwise-contract path in a
        module with low-precision evidence (bf16/fp16/fp8); the repo
        pins `jax_default_matmul_precision` only in conftest.py, so
        library code must pin per call
  N002  geometry-dependent reduction-order decomposition
        (psum_scatter / all_gather / all_to_all / ppermute — the
        psum -> reduce-scatter+all-gather class, plan-executor chunk
        reorders) reachable from a bitwise contract and not
        whitelisted parity-preserving in `[tool.numlint]`
  N003  quantize encode whose scale plane is discarded at the call
        site, or whose paired decode is never called project-wide
        (codec family registry, like storelint's key families)
  N004  checkpoint save-side dtype cast with no load-side dtype
        restore (save/load family registry) — the silent
        checkpoint-dtype-skew class
  N005  PRNG key consumed twice (or loop-consumed) without an
        intervening `split`/`fold_in` rebind on a token-exact or
        bitwise path
  N006  host nondeterminism feeding traced values: time-family /
        host-random calls or set-literal iteration inside a function
        distlint marks trace-context (R011's reachability)
  N007  test tolerance looser than the contract tier it verifies:
        bitwise/token_exact claims verified with ANY nonzero
        rtol/atol, tolerance claims verified looser than the
        decorator's declared envelope

Toolchain (human/json/SARIF, content-fingerprint baseline ratchet,
reasoned comment suppressions `# numlint: disable=Nnnn -- reason`,
`[tool.numlint]` config) is the shared `tools/_lintcore.py`.

Dynamic half (``--sweep``) — runs the registered contracts as REAL
programs across a geometry matrix (world size x data layout x
`TDX_PLANNER_FORCE` schedule, on CPU meshes), hashes outputs bitwise,
and on divergence bisects the jaxpr to the FIRST DIVERGENT EQN by
aligned prefix replay of the two program's flattened eqn streams.
``--seed-revert pr10`` re-runs the ZeRO-update subject with the
reduction order perturbed (the mean division reassociated into the
scatter — exactly the class PR 10's bitwise claim forbids) and
REQUIRES the sweeper to localize it per geometry, so the gate can
never silently lose its teeth (the storelint `--seed-revert pr16`
discipline, numerics edition). ``TDX_NUMLINT_SWEEP=quick`` (or
``--quick``) bounds each subject to its first two geometries for the
tier-1 run; the full matrix runs otherwise.
"""

from __future__ import annotations

import argparse
import ast
import fnmatch
import hashlib
import json
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

from ._lintcore import (
    SEVERITIES,
    Finding,
    apply_baseline,
    load_baseline,
    load_pyproject_section,
    parse_severity_table,
    parse_suppressions,
    render_report,
    render_sarif,
    write_baseline,
)
from .distlint import FunctionInfo, ModuleInfo, Project, build_project
from .distlint import LintConfig as _DistlintConfig
from .distlint import load_config as _load_distlint_config

__all__ = [
    "RULES",
    "NumlintConfig",
    "load_config",
    "harvest_contracts",
    "run_rules",
    "lint",
    "SUBJECTS",
    "run_sweep",
    "main",
]

RULES = {
    "N001": "matmul without pinned precision/preferred_element_type on a "
            "bitwise-contract path (low-precision module)",
    "N002": "geometry-dependent reduction-order decomposition reachable "
            "from a bitwise contract, not whitelisted parity-preserving",
    "N003": "quantize encode without a scale-plane-paired decode "
            "(scale discarded, or paired decoder never called)",
    "N004": "checkpoint save-side dtype cast with no load-side restore "
            "(save/load dtype skew)",
    "N005": "PRNG key reuse without split/fold_in rebind on a "
            "token-exact/bitwise path",
    "N006": "host nondeterminism (time/host-random/set iteration) inside "
            "a traced context",
    "N007": "test tolerance looser than the contract tier it verifies",
}

_INFO_URI = "https://github.com/dblakely/pytorch-distributed-example"

DEFAULT_PATHS = ["pytorch_distributed_example_tpu", "examples", "tests"]
# every fixture corpus carries DELIBERATE findings (distlint's, storelint's,
# and numlint's own rule corpora) and must stay out of the self-scan
DEFAULT_EXCLUDE = ["csrc/", "tests/fixtures/"]

# `path-glob::name-glob` pairs whose reduction-order decomposition is
# PROVED parity-preserving: the ZeRO wire shape (PR 10's bitwise-parity
# test covers exactly these three — psum_scatter chunk i sums in the
# same order psum sums element i, and the update's all-gather moves
# bits, it never re-reduces them).
DEFAULT_PARITY_PRESERVING = [
    "pytorch_distributed_example_tpu/parallel/zero.py::reduce_scatter_mean",
    "pytorch_distributed_example_tpu/parallel/zero.py::unshard",
    "pytorch_distributed_example_tpu/parallel/zero.py::shard_of",
]

# "encoder:decoder" trailing-name pairs — the scale-plane families.
DEFAULT_CODEC_FAMILIES = [
    "quantize_blockwise:dequantize_blockwise",
    "quantize_blockwise_fp8:dequantize_blockwise_fp8",
    "quantize_kv:dequantize_kv",
    "_wire_encode:_wire_decode",
]

# "save:load" trailing-name pairs for N004.
DEFAULT_CHECKPOINT_FAMILIES = [
    "save_checkpoint:load_checkpoint",
    "dcp_save:dcp_load",
]

# matmul-family trailing call names whose accumulation dtype floats with
# the backend unless pinned.
_MATMUL_NAMES = {
    "dot",
    "dot_general",
    "matmul",
    "einsum",
    "tensordot",
    "conv_general_dilated",
}

# evidence that a module actually mixes precisions (N001 stays quiet in
# pure-f32 code: the backend default is deterministic per geometry there,
# and the conftest pin covers test runs).
_LOW_PRECISION_RE = re.compile(
    r"bfloat16|bf16|float16|fp16|float8|fp8|e4m3|e5m2", re.IGNORECASE
)

# geometry-dependent decomposition surface for N002: each of these
# changes WHERE partial sums happen when the mesh changes.
_DECOMP_NAMES = {
    "psum_scatter",
    "all_gather",
    "all_to_all",
    "ppermute",
    "reduce_scatter",
    "all_gather_into_tensor",
    "reduce_scatter_tensor",
}

# jax.random samplers: consuming a key twice through these forks replay.
_SAMPLER_NAMES = {
    "normal",
    "uniform",
    "bernoulli",
    "categorical",
    "randint",
    "permutation",
    "choice",
    "gumbel",
    "exponential",
    "laplace",
    "truncated_normal",
    "bits",
}
# deriving ops: produce fresh keys, never "consume" for reuse purposes.
_KEY_DERIVE_NAMES = {"split", "fold_in", "PRNGKey", "key", "clone"}

_TIME_ATTRS = {
    "time": {"time", "time_ns", "perf_counter", "perf_counter_ns",
             "monotonic", "monotonic_ns"},
    "os": {"urandom", "getrandom"},
    "uuid": {"uuid1", "uuid4"},
    "random": {"random", "randint", "randrange", "shuffle", "choice",
               "sample", "getrandbits", "gauss"},
}

_TOLERANCE_FN_NAMES = {"allclose", "assert_allclose", "isclose"}

# strictness order for N007 (strictest governs when a test touches
# several contracts).
_TIER_RANK = {"bitwise": 2, "token_exact": 1, "tolerance": 0}


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


@dataclass
class NumlintConfig:
    paths: List[str] = field(default_factory=lambda: list(DEFAULT_PATHS))
    exclude: List[str] = field(default_factory=lambda: list(DEFAULT_EXCLUDE))
    parity_preserving: List[str] = field(
        default_factory=lambda: list(DEFAULT_PARITY_PRESERVING)
    )
    codec_families: List[str] = field(
        default_factory=lambda: list(DEFAULT_CODEC_FAMILIES)
    )
    checkpoint_families: List[str] = field(
        default_factory=lambda: list(DEFAULT_CHECKPOINT_FAMILIES)
    )
    severity: Dict[str, str] = field(default_factory=dict)

    def rule_severity(self, rule: str) -> str:
        return self.severity.get(rule, "error")


def load_config(root: str) -> NumlintConfig:
    """Read ``[tool.numlint]`` from ``<root>/pyproject.toml`` (missing
    file/section → defaults)."""
    cfg = NumlintConfig()
    section = load_pyproject_section(root, "numlint")
    for name in (
        "paths",
        "exclude",
        "parity_preserving",
        "codec_families",
        "checkpoint_families",
    ):
        if name in section:
            setattr(cfg, name, [str(p) for p in section[name]])
    cfg.severity = parse_severity_table(section, "numlint")
    return cfg


# ---------------------------------------------------------------------------
# contract harvest + reachability
# ---------------------------------------------------------------------------


@dataclass
class ContractSite:
    fi: FunctionInfo
    tier: str
    rtol: Optional[float]
    atol: Optional[float]
    line: int


def _num_literal(node: ast.AST) -> Optional[float]:
    """Numeric value of a literal (handles unary minus); None if not
    a literal — a computed tolerance is out of static reach."""
    if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
        return float(node.value)
    if (
        isinstance(node, ast.UnaryOp)
        and isinstance(node.op, ast.USub)
        and isinstance(node.operand, ast.Constant)
        and isinstance(node.operand.value, (int, float))
    ):
        return -float(node.operand.value)
    return None


def _decorator_contract(node: ast.AST) -> Optional[Tuple[str, Optional[float], Optional[float]]]:
    """(tier, rtol, atol) when ``node`` is a numerics_contract decorator."""
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    name = f.id if isinstance(f, ast.Name) else (
        f.attr if isinstance(f, ast.Attribute) else None
    )
    if name != "numerics_contract":
        return None
    tier = None
    if node.args and isinstance(node.args[0], ast.Constant):
        tier = node.args[0].value
    if not isinstance(tier, str):
        return None
    rtol = atol = None
    for kw in node.keywords:
        if kw.arg == "rtol":
            rtol = _num_literal(kw.value)
        elif kw.arg == "atol":
            atol = _num_literal(kw.value)
    return tier, rtol, atol


def harvest_contracts(project: Project) -> Dict[int, ContractSite]:
    """id(FunctionInfo) -> ContractSite for every decorated function,
    harvested from the AST (no module is imported)."""
    out: Dict[int, ContractSite] = {}
    for minfo in project.modules.values():
        for fi in minfo.functions.values():
            deco_list = getattr(fi.node, "decorator_list", None) or []
            for deco in deco_list:
                got = _decorator_contract(deco)
                if got is not None:
                    tier, rtol, atol = got
                    out[id(fi)] = ContractSite(
                        fi=fi,
                        tier=tier,
                        rtol=rtol,
                        atol=atol,
                        line=getattr(fi.node, "lineno", 1),
                    )
                    break
    return out


def contract_reach(
    project: Project, contracts: Dict[int, ContractSite]
) -> Dict[int, Dict[str, Tuple[str, ...]]]:
    """id(fi) -> {tier: chain} for every function reachable DOWN the
    call graph from a contracted function (the contracted function
    itself included, empty-suffix chain). BFS per contract root, so the
    recorded chain is a shortest path — the message a human debugs
    with."""
    reach: Dict[int, Dict[str, Tuple[str, ...]]] = {}
    for site in contracts.values():
        root = site.fi
        tier = site.tier
        seen: Set[int] = set()
        queue: List[Tuple[FunctionInfo, Tuple[str, ...]]] = [
            (root, (root.display,))
        ]
        while queue:
            fi, chain = queue.pop(0)
            if id(fi) in seen or len(chain) > 8:
                continue
            seen.add(id(fi))
            tiers = reach.setdefault(id(fi), {})
            if tier not in tiers:
                tiers[tier] = chain
            for _line, callee in fi.edges:
                if id(callee) not in seen:
                    queue.append((callee, chain + (callee.display,)))
    return reach


def _callee_contracts(
    fi: FunctionInfo,
    contracts: Dict[int, ContractSite],
    _depth: int = 0,
    _seen: Optional[Set[int]] = None,
) -> List[ContractSite]:
    """Contracted functions transitively CALLED by ``fi`` (the N007
    direction: does this test verify a contract?)."""
    if _seen is None:
        _seen = set()
    if _depth > 6 or id(fi) in _seen:
        return []
    _seen.add(id(fi))
    out: List[ContractSite] = []
    for _line, callee in fi.edges:
        site = contracts.get(id(callee))
        if site is not None:
            out.append(site)
        out.extend(_callee_contracts(callee, contracts, _depth + 1, _seen))
    return out


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------


def _trailing_name(call: ast.Call) -> Optional[str]:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _receiver_name(call: ast.Call) -> Optional[str]:
    """Leftmost Name of the call's receiver chain (`a` in a.b.c())."""
    f = call.func
    while isinstance(f, ast.Attribute):
        f = f.value
    if isinstance(f, ast.Name):
        return f.id
    return None


def _whitelisted(fi: FunctionInfo, patterns: Sequence[str]) -> bool:
    for pat in patterns:
        if "::" in pat:
            path_g, name_g = pat.split("::", 1)
        else:
            path_g, name_g = pat, "*"
        if fnmatch.fnmatch(fi.path, path_g) and fnmatch.fnmatch(
            fi.name, name_g
        ):
            return True
    return False


def _split_families(entries: Sequence[str], what: str) -> List[Tuple[str, str]]:
    out = []
    for e in entries:
        if ":" not in e:
            raise ValueError(
                f"[tool.numlint] {what} entry {e!r} must be 'producer:consumer'"
            )
        a, b = e.split(":", 1)
        out.append((a.strip(), b.strip()))
    return out


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------


def _emit(
    findings: List[Finding],
    cfg: NumlintConfig,
    path: str,
    node: ast.AST,
    rule: str,
    message: str,
    chain: Tuple[str, ...] = (),
) -> None:
    sev = cfg.rule_severity(rule)
    if sev == "off":
        return
    findings.append(
        Finding(
            path=path,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0) + 1,
            rule=rule,
            message=message,
            severity=sev,
            trace=chain,
        )
    )


def _rule_n001_n002(
    project: Project,
    cfg: NumlintConfig,
    reach: Dict[int, Dict[str, Tuple[str, ...]]],
    findings: List[Finding],
) -> None:
    for minfo in project.modules.values():
        low_prec_module = bool(_LOW_PRECISION_RE.search(minfo.src))
        for fi in minfo.functions.values():
            tiers = reach.get(id(fi))
            if not tiers or "bitwise" not in tiers:
                continue
            chain = tiers["bitwise"]
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                name = _trailing_name(node)
                if name in _MATMUL_NAMES and low_prec_module:
                    kwargs = {kw.arg for kw in node.keywords}
                    if not ({"precision", "preferred_element_type"} & kwargs):
                        _emit(
                            findings, cfg, fi.path, node, "N001",
                            f"`{name}` on the bitwise-contract path "
                            f"`{' -> '.join(chain)}` has no pinned "
                            "`precision=`/`preferred_element_type=` in a "
                            "module that mixes precisions; the repo-wide "
                            "jax_default_matmul_precision pin covers only "
                            "conftest.py, not "
                            "library callers",
                            chain,
                        )
                if name in _DECOMP_NAMES:
                    if _whitelisted(fi, cfg.parity_preserving):
                        continue
                    _emit(
                        findings, cfg, fi.path, node, "N002",
                        f"`{name}` decomposes the reduction order on the "
                        f"bitwise-contract path `{' -> '.join(chain)}`; "
                        "geometry changes reassociate its partial sums. "
                        "Prove parity and whitelist the enclosing "
                        "function under [tool.numlint] parity_preserving, "
                        "or demote the contract to 'tolerance'",
                        chain,
                    )


def _rule_n003(
    project: Project, cfg: NumlintConfig, findings: List[Finding]
) -> None:
    families = _split_families(cfg.codec_families, "codec_families")
    encoders = {enc: dec for enc, dec in families}
    # one project-wide pass: which trailing names are ever called?
    called: Set[str] = set()
    for minfo in project.modules.values():
        for node in ast.walk(minfo.tree):
            if isinstance(node, ast.Call):
                n = _trailing_name(node)
                if n:
                    called.add(n)
    for minfo in project.modules.values():
        for node in ast.walk(minfo.tree):
            # scale plane discarded at the assignment: q, _ = enc(...)
            if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call
            ):
                enc = _trailing_name(node.value)
                if enc in encoders and len(node.targets) == 1:
                    tgt = node.targets[0]
                    if (
                        isinstance(tgt, (ast.Tuple, ast.List))
                        and len(tgt.elts) >= 2
                        and isinstance(tgt.elts[1], ast.Name)
                        and tgt.elts[1].id.startswith("_")
                    ):
                        _emit(
                            findings, cfg, minfo.path, node, "N003",
                            f"`{enc}` scale plane bound to "
                            f"`{tgt.elts[1].id}` and discarded — the int8 "
                            "payload is undecodable without it (pair with "
                            f"`{encoders[enc]}`)",
                        )
            # payload-only projection: enc(...)[0]
            if (
                isinstance(node, ast.Subscript)
                and isinstance(node.value, ast.Call)
                and _trailing_name(node.value) in encoders
                and isinstance(node.slice, ast.Constant)
                and node.slice.value == 0
            ):
                enc = _trailing_name(node.value)
                _emit(
                    findings, cfg, minfo.path, node, "N003",
                    f"`{enc}(...)[0]` keeps the payload and drops the "
                    "scale plane — undecodable (pair with "
                    f"`{encoders[enc]}`)",
                )
            # encoder used while its paired decoder never appears
            if isinstance(node, ast.Call):
                enc = _trailing_name(node)
                if enc in encoders and encoders[enc] not in called:
                    _emit(
                        findings, cfg, minfo.path, node, "N003",
                        f"`{enc}` is called but its paired decoder "
                        f"`{encoders[enc]}` is never called anywhere in "
                        "the project — every consumer path reads raw "
                        "int8 without the scale plane",
                    )


def _local_subtrees(
    minfo: ModuleInfo, fi: FunctionInfo, depth: int = 2
) -> List[ast.AST]:
    """fi's body plus same-module helpers it calls (N004 looks through
    one save -> _to_host style hop)."""
    out = [fi.node]
    frontier = [fi.node]
    for _ in range(depth):
        nxt = []
        for sub in frontier:
            for node in ast.walk(sub):
                if isinstance(node, ast.Call):
                    name = _trailing_name(node)
                    callee = minfo.functions.get(name) if name else None
                    if callee is not None and callee.node not in out:
                        out.append(callee.node)
                        nxt.append(callee.node)
        frontier = nxt
    return out


def _rule_n004(
    project: Project, cfg: NumlintConfig, findings: List[Finding]
) -> None:
    families = _split_families(cfg.checkpoint_families, "checkpoint_families")
    # trailing name -> [(minfo, fi), ...]; a save is paired with the
    # load IN ITS OWN MODULE when one exists (checkpoint.py defines
    # both halves; so does each fixture), falling back to the first
    # project-wide definition for split save/load modules
    by_name: Dict[str, List[Tuple[ModuleInfo, FunctionInfo]]] = {}
    for minfo in project.modules.values():
        for fi in minfo.functions.values():
            tail = fi.name.rsplit(".", 1)[-1]
            by_name.setdefault(tail, []).append((minfo, fi))
    for save_name, load_name in families:
        loads = by_name.get(load_name, [])
        if not loads:
            continue
        for save_minfo, save_fi in by_name.get(save_name, []):
            load_minfo, load_fi = next(
                (
                    (lm, lf)
                    for lm, lf in loads
                    if lm.name == save_minfo.name
                ),
                loads[0],
            )
            cast_sites = [
                node
                for sub in _local_subtrees(save_minfo, save_fi)
                for node in ast.walk(sub)
                if isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "astype"
            ]
            if not cast_sites:
                continue
            load_blob = "\n".join(
                ast.dump(sub)
                for sub in _local_subtrees(load_minfo, load_fi)
            )
            if "astype" in load_blob or "dtype" in load_blob:
                continue
            for node in cast_sites:
                _emit(
                    findings, cfg, save_minfo.path, node, "N004",
                    f"`{save_name}` casts leaves with `.astype` on the "
                    f"way out but `{load_name}` never restores dtypes "
                    "(no astype and no dtype manifest read) — a "
                    "round-trip silently re-types the live param tree",
                )


class _KeyFlow:
    """Linear-ish per-function key-consumption walker for N005."""

    def __init__(
        self,
        cfg: NumlintConfig,
        path: str,
        chain: Tuple[str, ...],
        findings: List[Finding],
    ):
        self.cfg = cfg
        self.path = path
        self.chain = chain
        self.findings = findings

    # -- expression scan: returns names consumed by samplers, in order
    def _consumptions(self, node: ast.AST) -> List[Tuple[str, ast.Call]]:
        out = []
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                name = _trailing_name(sub)
                if name in _SAMPLER_NAMES and sub.args:
                    arg = sub.args[0]
                    if isinstance(arg, ast.Name):
                        out.append((arg.id, sub))
        return out

    def _assigned_names(self, stmt: ast.stmt) -> Set[str]:
        names: Set[str] = set()
        targets: List[ast.AST] = []
        if isinstance(stmt, ast.Assign):
            targets = list(stmt.targets)
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            targets = [stmt.target]
        elif isinstance(stmt, ast.For):
            targets = [stmt.target]
        for t in targets:
            for sub in ast.walk(t):
                if isinstance(sub, ast.Name):
                    names.add(sub.id)
        return names

    def run(self, body: List[ast.stmt], state: Dict[str, str]) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested defs have their own FunctionInfo/reach
            if isinstance(stmt, (ast.For, ast.While)):
                rebound = set()
                for inner in ast.walk(stmt):
                    if isinstance(inner, ast.stmt):
                        rebound |= self._assigned_names(inner)
                loop_body = stmt.body + getattr(stmt, "orelse", [])
                for name, call in self._consumptions(
                    ast.Module(body=loop_body, type_ignores=[])
                ):
                    if name in state and name not in rebound:
                        self._fire(name, call, looped=True)
                        state[name] = "consumed"
                # run the body once for ordinary double-use inside it
                self.run(loop_body, state)
                continue
            if isinstance(stmt, ast.If):
                s1, s2 = dict(state), dict(state)
                self.run(stmt.body, s1)
                self.run(stmt.orelse, s2)
                for k in set(s1) | set(s2):
                    if s1.get(k) == "consumed" or s2.get(k) == "consumed":
                        state[k] = "consumed"
                    else:
                        state[k] = s1.get(k, s2.get(k, "fresh"))
                continue
            # plain statement: consumptions left-to-right, then rebinds
            for name, call in self._consumptions(stmt):
                if state.get(name) == "consumed":
                    self._fire(name, call, looped=False)
                else:
                    state[name] = "consumed"
            for name in self._assigned_names(stmt):
                state[name] = "fresh"

    def _fire(self, name: str, call: ast.Call, looped: bool) -> None:
        how = (
            "consumed on every loop iteration without a split/fold_in "
            "rebind inside the loop"
            if looped
            else "consumed twice without an intervening split/fold_in "
            "rebind"
        )
        _emit(
            self.findings, self.cfg, self.path, call, "N005",
            f"PRNG key `{name}` {how} on the contract path "
            f"`{' -> '.join(self.chain)}` — identical samples / forked "
            "replay",
            self.chain,
        )


def _rule_n005(
    project: Project,
    cfg: NumlintConfig,
    reach: Dict[int, Dict[str, Tuple[str, ...]]],
    findings: List[Finding],
) -> None:
    for minfo in project.modules.values():
        for fi in minfo.functions.values():
            tiers = reach.get(id(fi))
            if not tiers:
                continue
            tier = (
                "token_exact" if "token_exact" in tiers
                else ("bitwise" if "bitwise" in tiers else None)
            )
            if tier is None:
                continue
            chain = tiers[tier]
            body = getattr(fi.node, "body", None)
            if not body:
                continue
            state: Dict[str, str] = {}
            # parameters named like keys start live
            args = getattr(fi.node, "args", None)
            if args is not None:
                for a in args.posonlyargs + args.args + args.kwonlyargs:
                    if re.search(r"key|rng|seed", a.arg, re.IGNORECASE):
                        state[a.arg] = "fresh"
            _KeyFlow(cfg, fi.path, chain, findings).run(body, state)


def _rule_n006(
    project: Project, cfg: NumlintConfig, findings: List[Finding]
) -> None:
    for minfo in project.modules.values():
        # does bare `random` here mean the stdlib module?
        random_is_std = minfo.import_aliases.get("random") == "random"
        for fi in minfo.functions.values():
            if fi.trace_ctx is None:
                continue
            where = fi.trace_ctx.describe()
            for node in ast.walk(fi.node):
                if isinstance(node, ast.Call):
                    recv = _receiver_name(node)
                    name = _trailing_name(node)
                    mod_attrs = _TIME_ATTRS.get(recv or "", set())
                    if name in mod_attrs:
                        if recv == "random" and not random_is_std:
                            continue
                        _emit(
                            findings, cfg, fi.path, node, "N006",
                            f"host call `{recv}.{name}()` inside a traced "
                            f"context ({where}) — its value is baked into "
                            "the trace on ONE rank/run and replayed on "
                            "every other (nondeterministic constant "
                            "folding)",
                        )
                if isinstance(node, ast.For):
                    it = node.iter
                    is_set = isinstance(it, ast.Set) or (
                        isinstance(it, ast.Call)
                        and _trailing_name(it) == "set"
                    )
                    if is_set:
                        _emit(
                            findings, cfg, fi.path, node, "N006",
                            "iteration over a set inside a traced context "
                            f"({where}) — set order is hash-seed "
                            "dependent, so the traced program differs "
                            "between processes",
                        )


def _rule_n007(
    project: Project,
    cfg: NumlintConfig,
    contracts: Dict[int, ContractSite],
    findings: List[Finding],
) -> None:
    for minfo in project.modules.values():
        for fi in minfo.functions.values():
            tail = fi.name.rsplit(".", 1)[-1]
            if not tail.startswith("test_"):
                continue
            sites = _callee_contracts(fi, contracts)
            if not sites:
                continue
            strictest = max(sites, key=lambda s: _TIER_RANK[s.tier])
            for node in ast.walk(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                if _trailing_name(node) not in _TOLERANCE_FN_NAMES:
                    continue
                tols: Dict[str, float] = {}
                for kw in node.keywords:
                    if kw.arg in ("rtol", "atol"):
                        v = _num_literal(kw.value)
                        if v is not None:
                            tols[kw.arg] = v
                if not tols:
                    continue  # exact-default or non-literal: out of reach
                if strictest.tier in ("bitwise", "token_exact"):
                    loose = {k: v for k, v in tols.items() if v > 0.0}
                    if loose:
                        _emit(
                            findings, cfg, fi.path, node, "N007",
                            f"test verifies `{strictest.fi.display}` "
                            f"({strictest.tier} contract) with "
                            + ", ".join(
                                f"{k}={v:g}" for k, v in sorted(loose.items())
                            )
                            + " — a bitwise/token-exact claim admits NO "
                            "tolerance; compare exactly (or suppress with "
                            "the reason this assertion checks a different "
                            "property)",
                        )
                else:
                    over = []
                    if (
                        strictest.rtol is not None
                        and tols.get("rtol", 0.0) > strictest.rtol
                    ):
                        over.append(
                            f"rtol={tols['rtol']:g} > declared "
                            f"{strictest.rtol:g}"
                        )
                    if (
                        strictest.atol is not None
                        and tols.get("atol", 0.0) > strictest.atol
                    ):
                        over.append(
                            f"atol={tols['atol']:g} > declared "
                            f"{strictest.atol:g}"
                        )
                    if over:
                        _emit(
                            findings, cfg, fi.path, node, "N007",
                            f"test verifies `{strictest.fi.display}` "
                            "looser than its declared tolerance envelope "
                            f"({'; '.join(over)}) — the test would pass "
                            "on a codec that violates the claim",
                        )


# ---------------------------------------------------------------------------
# suppressions, fingerprints, lint()
# ---------------------------------------------------------------------------


def _apply_suppressions(findings: List[Finding], project: Project) -> None:
    cache: Dict[str, Tuple[Dict[int, Set[str]], Dict[str, int]]] = {}
    for f in findings:
        minfo = project.by_path.get(f.path)
        if minfo is None:
            continue
        if f.path not in cache:
            cache[f.path] = parse_suppressions(minfo.src, "numlint")
        per_line, file_wide = cache[f.path]
        if f.rule in per_line.get(f.line, set()) or f.rule in file_wide:
            f.suppressed = True


def _assign_fingerprints(findings: List[Finding]) -> None:
    """Content fingerprints over (path, rule, salient token) with an
    occurrence counter — stable across unrelated line moves."""
    occ: Dict[Tuple[str, str, str], int] = {}
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.col)):
        m = re.search(r"`([^`]+)`", f.message)
        salient = m.group(1) if m else f.message[:60]
        key = (f.path, f.rule, salient)
        n = occ.get(key, 0)
        occ[key] = n + 1
        f.fingerprint = hashlib.sha1(
            f"{f.path}\x00{f.rule}\x00{salient}\x00{n}".encode()
        ).hexdigest()[:16]


def run_rules(
    project: Project, cfg: NumlintConfig
) -> List[Finding]:
    contracts = harvest_contracts(project)
    reach = contract_reach(project, contracts)
    findings: List[Finding] = []
    _rule_n001_n002(project, cfg, reach, findings)
    _rule_n003(project, cfg, findings)
    _rule_n004(project, cfg, findings)
    _rule_n005(project, cfg, reach, findings)
    _rule_n006(project, cfg, findings)
    _rule_n007(project, cfg, contracts, findings)
    # nested defs are walked inside their enclosing function too — dedup
    seen: Set[Tuple[str, int, int, str]] = set()
    uniq: List[Finding] = []
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.col, f.rule)):
        key = (f.path, f.line, f.col, f.rule)
        if key not in seen:
            seen.add(key)
            uniq.append(f)
    return uniq


def lint(
    root: str = ".", config: Optional[NumlintConfig] = None
) -> Tuple[List[Finding], Project]:
    """The full static half: project build (distlint's call graph with
    numlint's path scope), contract harvest, rules, suppressions,
    fingerprints."""
    config = config or load_config(root)
    dl_cfg = _load_distlint_config(root)
    dl_cfg.paths = list(config.paths)
    dl_cfg.exclude = list(config.exclude)
    project = build_project(config.paths, root, dl_cfg)
    findings = run_rules(project, config)
    _apply_suppressions(findings, project)
    _assign_fingerprints(findings)
    return findings, project


# ---------------------------------------------------------------------------
# dynamic half: geometry parity sweep
# ---------------------------------------------------------------------------
#
# Each SUBJECT realizes one registered contract as a real compiled
# program and runs it across a geometry matrix. Outputs are hashed
# BITWISE; a bitwise-tier divergence (or a tolerance-tier envelope
# violation) triggers jaxpr bisection to the first divergent eqn.


def _harness_xla_flags(flags: str) -> str:
    """``flags`` plus what a bitwise sweep needs of XLA:CPU — the same
    two settings conftest.py exports for the suite:

    * 8 virtual devices (the geometry matrix goes to world 4 and 8);
    * the fusion emitters OFF. On jax 0.9.0 they contract ``a*b + c``
      into one FMA or not depending on how XLA happened to fuse the
      surrounding ops, so two programs with identical arithmetic differ
      in the last bit wherever ``a*b`` is inexact (``g * (1/3)``: any
      non-power-of-two world). Measured at world 3: the sharded and the
      unsharded momentum disagreed in 9 of 37 elements with no change
      to either program's math, and the seeded PR 10 reassociation
      stopped being visible. A bitwise contract is a statement about
      the program's arithmetic, not about a backend's fusion choices.
    """
    if "xla_force_host_platform_device_count" not in flags:
        flags += " --xla_force_host_platform_device_count=8"
    if "xla_cpu_use_fusion_emitters" not in flags:
        flags += " --xla_cpu_use_fusion_emitters=false"
    return flags.strip()


def _ensure_cpu_jax() -> None:
    """Mirror conftest.py's environment for a standalone CLI run: 8
    virtual CPU devices + the matmul-precision pin (N001 cites it); the
    PRNG stream is the installed default, as in the suite.
    Must run BEFORE the first jax import in this process."""
    if "jax" not in sys.modules:
        os.environ["XLA_FLAGS"] = _harness_xla_flags(
            os.environ.get("XLA_FLAGS", "")
        )
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")


def _tree_hash(values) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(values):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()[:16]


def _flat_eqn_descriptors(closed_jaxpr) -> List[str]:
    """Flattened eqn stream, recursing through pjit/shard_map/scan/...
    sub-jaxprs — the alignment axis for first-divergent-eqn bisection."""
    out: List[str] = []

    def visit(jaxpr) -> None:
        for eqn in jaxpr.eqns:
            subs = []
            for v in eqn.params.values():
                stack = [v]
                while stack:
                    item = stack.pop()
                    if hasattr(item, "eqns"):  # Jaxpr
                        subs.append(item)
                    elif hasattr(item, "jaxpr") and hasattr(
                        item.jaxpr, "eqns"
                    ):  # ClosedJaxpr
                        subs.append(item.jaxpr)
                    elif isinstance(item, (tuple, list)):
                        stack.extend(item)
            if subs:
                out.append(f"{eqn.primitive.name}(...)")
                for s in subs:
                    visit(s)
            else:
                ins = ",".join(
                    str(getattr(v, "aval", "?")) for v in eqn.invars
                )
                outs = ",".join(
                    str(getattr(v, "aval", "?")) for v in eqn.outvars
                )
                axis = eqn.params.get("axis_name")
                tag = f"[axis={axis}]" if axis is not None else ""
                out.append(f"{eqn.primitive.name}{tag} {ins} -> {outs}")

    visit(closed_jaxpr.jaxpr)
    return out


def _value_prefix_replay(fn_a, fn_b, args) -> Optional[str]:
    """Eqn-by-eqn lockstep eval of two STRUCTURALLY IDENTICAL jaxprs,
    comparing every intermediate bitwise; the first eqn whose outputs
    differ is the numerical divergence point. Only possible for
    collective-free top-level programs (a collective prim cannot bind
    outside its mesh context) — callers fall back to the structural
    report or a leaf diff."""
    import jax
    import numpy as np

    ja = jax.make_jaxpr(fn_a)(*args)
    jb = jax.make_jaxpr(fn_b)(*args)
    if len(ja.jaxpr.eqns) != len(jb.jaxpr.eqns):
        return None

    def run(jx):
        env: Dict[Any, Any] = {}

        def read(v):
            if hasattr(v, "val"):
                return v.val
            return env[v]

        flat = jax.tree_util.tree_leaves(args)
        for var, val in zip(jx.jaxpr.invars, flat):
            env[var] = val
        for cv, val in zip(jx.jaxpr.constvars, jx.consts):
            env[cv] = val
        trace: List[List[Any]] = []
        for eqn in jx.jaxpr.eqns:
            invals = [read(v) for v in eqn.invars]
            outs = eqn.primitive.bind(*invals, **eqn.params)
            if not eqn.primitive.multiple_results:
                outs = [outs]
            for var, val in zip(eqn.outvars, outs):
                env[var] = val
            trace.append(outs)
        return trace

    try:
        ta, tb = run(ja), run(jb)
    except Exception:
        return None
    for i, (oa, ob) in enumerate(zip(ta, tb)):
        for la, lb in zip(oa, ob):
            na, nb = np.asarray(la), np.asarray(lb)
            if na.tobytes() != nb.tobytes():
                delta = float(
                    np.max(np.abs(na.astype("f8") - nb.astype("f8")))
                )
                prim = ja.jaxpr.eqns[i].primitive.name
                return (
                    f"first divergent eqn #{i + 1}: `{prim}` outputs "
                    f"differ (max |delta| = {delta:.3g})"
                )
    return None


def first_divergence(fn_a, fn_b, args) -> str:
    """Localize why two program variants diverge: structural alignment
    over the flattened eqn streams first (a reordered reduction shows
    up HERE — the PR 10 revert class), value prefix replay when the
    streams are structurally identical."""
    import jax

    da = _flat_eqn_descriptors(jax.make_jaxpr(fn_a)(*args))
    db = _flat_eqn_descriptors(jax.make_jaxpr(fn_b)(*args))
    for i, (a, b) in enumerate(zip(da, db)):
        if a != b:
            return (
                f"first divergent eqn #{i + 1}: subject `{a}` vs "
                f"reference `{b}`"
            )
    if len(da) != len(db):
        i = min(len(da), len(db))
        longer = da if len(da) > len(db) else db
        who = "subject" if len(da) > len(db) else "reference"
        return (
            f"first divergent eqn #{i + 1}: {who} carries extra eqn "
            f"`{longer[i]}`"
        )
    replayed = _value_prefix_replay(fn_a, fn_b, args)
    if replayed is not None:
        return replayed
    return (
        "jaxprs structurally identical over "
        f"{len(da)} eqns; divergence is value-level inside a mesh "
        "context (prefix replay cannot bind collectives host-side)"
    )


# -- subjects ---------------------------------------------------------------


def _det_array(n: int, scale: float = 0.37, bias: float = 1.23):
    """Deterministic non-trivial-mantissa data (no host RNG — N006)."""
    import jax.numpy as jnp

    i = jnp.arange(n, dtype=jnp.float32)
    return jnp.sin(i * scale + bias) * (1.0 + 0.01 * i)


def _zero_update_build(world: int, rs_impl=None):
    """(fn, args): the ZeRO-sharded momentum-SGD update over a CPU mesh
    of ``world`` devices, returning updated params from every rank —
    mirrors tests/test_zero_update.py's parity harness without needing
    a process gang."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from .._compat import shard_map_fn
    from ..parallel import zero

    rs = rs_impl or zero.reduce_scatter_mean
    n, steps, lr, mom = 37, 2, 0.1, 0.9
    mesh = Mesh(np.array(jax.devices()[:world]), ("r",))

    def body(g_local, p_full):
        g_local = g_local[0]  # (steps, n)
        idx = jax.lax.axis_index("r")
        psh = zero.shard_of(p_full, idx, world)
        msh = jnp.zeros_like(psh)
        for s in range(steps):
            gsh = rs(g_local[s], "r", world)
            msh = mom * msh + gsh
            psh = psh - lr * msh
        return zero.unshard(psh, "r", (n,), p_full.dtype)[None]

    fn = jax.jit(
        shard_map_fn(
            body, mesh=mesh, in_specs=(P("r"), P()), out_specs=P("r")
        )
    )
    G = _det_array(world * steps * n).reshape(world, steps, n)
    p = _det_array(n, scale=0.11, bias=0.7)
    return fn, (G, p)


def _zero_reference(world: int):
    """Unsharded DDP update (psum-mean then full elementwise update) —
    the PR 10 reference the sharded path must match bitwise."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from .._compat import shard_map_fn

    n, steps, lr, mom = 37, 2, 0.1, 0.9
    mesh = Mesh(np.array(jax.devices()[:world]), ("r",))

    def body(g_local, p_full):
        g_local = g_local[0]
        m = jnp.zeros_like(p_full)
        p = p_full
        for s in range(steps):
            gbar = jax.lax.psum(g_local[s], "r") / world
            m = mom * m + gbar
            p = p - lr * m
        return p[None]

    fn = jax.jit(
        shard_map_fn(
            body, mesh=mesh, in_specs=(P("r"), P()), out_specs=P("r")
        )
    )
    G = _det_array(world * steps * n).reshape(world, steps, n)
    p = _det_array(n, scale=0.11, bias=0.7)
    return fn, (G, p)


def _perturbed_reduce_scatter_mean(leaf, axis_name: str, world: int):
    """The seeded PR 10 revert: the mean division reassociated INTO the
    scatter (sum(x)/w -> sum(x/w)) — same collectives, same shapes,
    different reduction order, bitwise-divergent in float."""
    from jax import lax

    from ..parallel import zero

    flat = zero.padded_flat(leaf, world)
    return lax.psum_scatter(flat / world, axis_name, tiled=True)


def _run_zero_update(geom: Dict[str, Any], rs_impl=None) -> Dict[str, Any]:
    import numpy as np

    world = geom["world"]
    sub_fn, sub_args = _zero_update_build(world, rs_impl=rs_impl)
    ref_fn, ref_args = _zero_reference(world)
    sub = np.asarray(sub_fn(*sub_args))
    ref = np.asarray(ref_fn(*ref_args))
    ok = sub.tobytes() == ref.tobytes()
    detail = ""
    if not ok:
        # bisect the SHARDED variant against the unperturbed sharded
        # build when an impl override diverged (the seed-revert path);
        # against the reference program otherwise
        if rs_impl is not None:
            base_fn, _ = _zero_update_build(world)
            detail = first_divergence(sub_fn, base_fn, sub_args)
        else:
            detail = first_divergence(sub_fn, ref_fn, sub_args)
        delta = float(np.max(np.abs(sub - ref)))
        detail += f"; max output |delta| = {delta:.3g}"
    return {"ok": ok, "detail": detail, "hash": _tree_hash(sub)}


def _run_planned_allreduce(geom: Dict[str, Any]) -> Dict[str, Any]:
    """world x algorithm x lowering-mode parity for planned all-reduce.

    ``mode``:
      - ``eager``        — the eager planner's `driver.compiled_body`
        (the original subject);
      - ``traced``       — the in-jit dispatch seam (`plan/traced.py`)
        reading a seeded agreed-table entry, the lowering TP/FSDP/ZeRO
        call sites emit after `prepare()`;
      - ``traced_force`` — the same seam driven by `TDX_PLANNER_FORCE`
        honored inside the trace (empty table).

    Traced modes must be BITWISE the eager compiled body for the same
    algorithm (both lower the identical `driver.body_for` rounds); a
    mismatch is bisected to the first divergent jaxpr eqn.  All modes
    keep the original contracts: ranks bitwise-agree with each other,
    and sit inside the 1e-5 envelope of the exact f32 sum."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from .._compat import shard_map_fn
    from ..plan import driver, traced

    world, alg = geom["world"], geom["schedule"]
    mode = geom.get("mode", "eager")
    mesh = Mesh(np.array(jax.devices()[:world]), ("r",))
    eager_prog = driver.compiled_body("all_reduce", alg, world, "r", mesh)
    x = _det_array(world * 64).reshape(world, 64)

    env_keys = ("TDX_COLLECTIVE_PLANNER", "TDX_PLANNER_FORCE",
                "TDX_PLANNER_OVERLAP")
    saved = {k: os.environ.get(k) for k in env_keys}
    try:
        if mode == "eager":
            sub_fn = eager_prog
        else:
            if mode == "traced_force":
                # force env honored inside the trace; table left empty
                traced.reset()
                os.environ["TDX_COLLECTIVE_PLANNER"] = "1"
                os.environ["TDX_PLANNER_FORCE"] = alg
            else:
                # the prepare()-agreed table path, planner env neutral
                traced.reset()
                os.environ.pop("TDX_PLANNER_FORCE", None)
                traced.seed(
                    "all_reduce", alg, world=world, nbytes=64 * 4,
                    source="numlint-sweep",
                )
            sub_fn = jax.jit(shard_map_fn(
                lambda t: traced.all_reduce(t, "r", reduce_kind="sum"),
                mesh=mesh, in_specs=P("r"), out_specs=P("r"),
            ))
        out = np.asarray(sub_fn(x))
        exact = np.asarray(jnp.sum(x, axis=0, dtype=jnp.float32))
        # determinism: every rank must hold bit-identical results
        rows_agree = all(
            out[r].tobytes() == out[0].tobytes() for r in range(world)
        )
        env_ok = bool(
            np.allclose(out[0], exact, rtol=1e-5, atol=1e-5)
        )
        detail = ""
        traced_ok = True
        if mode != "eager":
            ref = np.asarray(eager_prog(x))
            traced_ok = out.tobytes() == ref.tobytes()
            if not traced_ok:
                detail = (
                    f"traced lowering diverges bitwise from the eager "
                    f"compiled body for schedule '{alg}'; "
                    + first_divergence(sub_fn, eager_prog, (x,))
                    + f"; max output |delta| = "
                    f"{float(np.max(np.abs(out - ref))):.3g}"
                )
        ok = rows_agree and env_ok and traced_ok
        if not detail:
            if not rows_agree:
                detail = "ranks disagree bitwise on the all-reduce result"
            elif not env_ok:
                detail = (
                    f"envelope violated: max |delta| = "
                    f"{float(np.max(np.abs(out[0] - exact))):.3g}"
                )
        return {"ok": ok, "detail": detail, "hash": _tree_hash(out)}
    finally:
        if mode != "eager":
            traced.reset()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _run_codec_roundtrip(geom: Dict[str, Any]) -> Dict[str, Any]:
    import jax.numpy as jnp
    import numpy as np

    from ..ops import quant

    x = _det_array(4 * 64).reshape(4, 64)
    if geom["codec"] == "kv":
        q, s = quant.quantize_kv(x)
        dq = quant.dequantize_kv(q, s, jnp.float32)
        bound = np.asarray(s)[..., None] * 0.5 + 1e-7
    else:
        bs = geom["block"]
        q, s = quant.quantize_blockwise(x, bs)
        dq = quant.dequantize_blockwise(q, s, bs)
        bound = (
            np.repeat(np.asarray(s), bs, axis=-1).reshape(x.shape) * 0.5
            + 1e-7
        )
    err = np.abs(np.asarray(dq) - np.asarray(x))
    ok = bool((err <= bound).all())
    detail = ""
    if not ok:
        worst = float(np.max(err - bound))
        detail = (
            f"round-trip error exceeds the scale/2 envelope by {worst:.3g}"
        )
        replay = _value_prefix_replay(
            lambda a: quant.dequantize_blockwise(
                *quant.quantize_blockwise(a, geom.get("block", 64)),
                geom.get("block", 64),
            ),
            lambda a: a,
            (x,),
        )
        if replay:
            detail += f"; {replay}"
    return {"ok": ok, "detail": detail, "hash": _tree_hash(dq)}


def _run_prng_stream(geom: Dict[str, Any]) -> Dict[str, Any]:
    """Token-exact subject: per-request fold_in streams must not depend
    on batch packing (the serve resize claim in miniature) — computing
    8 request streams in `world` chunks must equal one full batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    world = geom["world"]
    R, T, V = 8, 12, 17
    base = jax.random.PRNGKey(7)
    logits = _det_array(V)

    def stream(ids):
        cols = []
        for t in range(T):
            def tok(rid):
                k = jax.random.fold_in(jax.random.fold_in(base, rid), t)
                return jax.random.categorical(k, logits)

            cols.append(jax.vmap(tok)(ids))
        return jnp.stack(cols, axis=1)

    jitted = jax.jit(stream)
    full = np.asarray(jitted(jnp.arange(R)))
    chunks = [
        np.asarray(jitted(jnp.arange(R)[i::world])) for i in range(world)
    ]
    merged = np.empty_like(full)
    for i in range(world):
        merged[i::world] = chunks[i]
    ok = merged.tobytes() == full.tobytes()
    detail = ""
    if not ok:
        bad = np.argwhere(merged != full)
        r, t = (int(bad[0][0]), int(bad[0][1])) if len(bad) else (-1, -1)
        detail = (
            f"token stream forked at request {r}, step {t} when batched "
            f"in {world} chunks"
        )
    return {"ok": ok, "detail": detail, "hash": _tree_hash(full)}


def _geoms_zero(quick: bool) -> List[Dict[str, Any]]:
    # world=3 is load-bearing: mean division by a power-of-two world is
    # EXACT in IEEE, so a reassociated `/world` (the pr10 revert class)
    # is bitwise-invisible at 2 and 4 — only a non-power-of-two world
    # exposes it. Sweeping geometries is the whole point.
    worlds = [2, 3] if quick else [1, 2, 3, 4]
    return [{"world": w} for w in worlds]


def _geoms_plan(quick: bool) -> List[Dict[str, Any]]:
    # world x algorithm x TDX_PLANNER_FORCE x eager/traced lowering:
    # modes innermost, traced seam first, so the two-geometry quick
    # slice covers agreed-table + force-env dispatch on the smallest
    # geometry (each traced run rebuilds and compares against the
    # eager program anyway, so eager coverage rides along)
    from ..plan import driver

    forced = os.environ.get("TDX_PLANNER_FORCE")
    out = []
    for world in (2, 4):
        for alg in ("ring", "rhd", "hier"):
            if forced and alg != forced:
                continue
            if not driver.supports("all_reduce", alg, world):
                continue
            for mode in ("traced", "traced_force", "eager"):
                out.append(
                    {"world": world, "schedule": alg, "mode": mode}
                )
    return out[:2] if quick else out


def _geoms_codec(quick: bool) -> List[Dict[str, Any]]:
    out = [
        {"codec": "blockwise", "block": 8},
        {"codec": "blockwise", "block": 32},
        {"codec": "kv"},
    ]
    return out[:2] if quick else out


def _geoms_prng(quick: bool) -> List[Dict[str, Any]]:
    worlds = [1, 2] if quick else [1, 2, 4]
    return [{"world": w} for w in worlds]


def _run_disagg_migration(geom: Dict[str, Any]) -> Dict[str, Any]:
    """Token-exact subject for the disagg migration plane (serve/
    disagg/): every completion routed prefill-pool → KV migration →
    decode-pool must be bitwise the colocated engine's, at BOTH
    sampling modes (greedy and temperature>0 — the carry key must
    survive the pool hop), across heterogeneous prefill/decode TP
    degrees and the int8 KV pool."""
    import jax
    import numpy as np

    from ..mesh import init_device_mesh
    from ..models import TransformerConfig, TransformerLM
    from ..serve.disagg import DisaggRouter
    from ..serve.engine import ServeEngine
    from ..store import HashStore

    p_tp, d_tp = geom["prefill_tp"], geom["decode_tp"]
    kv_quant = geom["kv_quant"]
    if len(jax.devices()) < max(p_tp, d_tp):
        return {
            "ok": False,
            "detail": f"needs {max(p_tp, d_tp)} devices, "
            f"have {len(jax.devices())}",
            "hash": "",
        }

    cfg = TransformerConfig(
        vocab_size=64,
        d_model=32,
        n_layers=2,
        n_heads=4,
        max_seq_len=32,
        use_flash=False,
    )
    model = TransformerLM(cfg)
    params = model.init(
        jax.random.PRNGKey(0), np.zeros((1, 4), np.int32)
    )

    def mesh_for(n):
        if n == 1:
            return None
        return init_device_mesh(("tp",), (n,), devices=jax.devices()[:n])

    def make(role, tp, temperature, top_k):
        return ServeEngine(
            model,
            params,
            slots=4,
            temperature=temperature,
            top_k=top_k,
            block_size=4,
            pool_blocks=64,
            prefill_chunk_tokens=8,
            mesh=mesh_for(tp),
            kv_quant=kv_quant,
            role=role,
        )

    gen = np.random.default_rng(3)
    prompts = [
        gen.integers(0, 64, (n,)).astype(np.int32) for n in (5, 9, 13)
    ]

    def drive(submit, run):
        for i, p in enumerate(prompts):
            submit(p, 6, rid=f"r{i}", seed=11 + i)
        return {rid: c.tokens for rid, c in run().items()}

    mismatches = []
    hashes = []
    for mode, (temp, top_k) in (
        ("greedy", (0.0, None)),
        ("sampled", (0.8, 8)),
    ):
        colo = make("both", p_tp, temp, top_k)

        def run_colo(eng=colo):
            for _ in range(4096):
                if not eng.step():
                    break
            return eng.completions

        base = drive(colo.submit, run_colo)
        router = DisaggRouter(
            HashStore(),
            lambda i: make("prefill", p_tp, temp, top_k),
            lambda i: make("decode", d_tp, temp, top_k),
            chunk_blocks=2,
        )
        got = drive(router.submit, lambda: router.run(max_steps=4096))
        for rid in sorted(base):
            if got.get(rid) != base[rid]:
                mismatches.append(
                    f"{mode}/{rid}: colocated={base[rid]} "
                    f"disagg={got.get(rid)}"
                )
        if router.migrations == 0:
            mismatches.append(
                f"{mode}: no migrations occurred — the disagg path "
                "was not exercised"
            )
        hashes.append(
            _tree_hash([np.asarray(base[r]) for r in sorted(base)])
        )
    ok = not mismatches
    return {
        "ok": ok,
        "detail": "; ".join(mismatches[:3]),
        "hash": _tree_hash(hashes),
    }


def _geoms_disagg(quick: bool) -> List[Dict[str, Any]]:
    # heterogeneous TP on both sides of the migration plus the int8 KV
    # pool: raw block transport must be invisible at every combination
    out = [
        {"prefill_tp": 1, "decode_tp": 1, "kv_quant": False},
        {"prefill_tp": 1, "decode_tp": 2, "kv_quant": True},
        {"prefill_tp": 2, "decode_tp": 1, "kv_quant": False},
        {"prefill_tp": 2, "decode_tp": 2, "kv_quant": True},
        {"prefill_tp": 1, "decode_tp": 1, "kv_quant": True},
        {"prefill_tp": 2, "decode_tp": 1, "kv_quant": True},
    ]
    return out[:2] if quick else out


@dataclass
class Subject:
    name: str
    tier: str
    contract: str  # the registered contract this realizes
    geometries: Callable[[bool], List[Dict[str, Any]]]
    run: Callable[[Dict[str, Any]], Dict[str, Any]]


SUBJECTS: Dict[str, Subject] = {
    "zero_update": Subject(
        "zero_update",
        "bitwise",
        "pytorch_distributed_example_tpu.parallel.ddp:make_ddp_train_step",
        _geoms_zero,
        _run_zero_update,
    ),
    "planned_allreduce": Subject(
        "planned_allreduce",
        "tolerance",
        "pytorch_distributed_example_tpu.ops.quant:quantized_all_reduce",
        _geoms_plan,
        _run_planned_allreduce,
    ),
    "codec_roundtrip": Subject(
        "codec_roundtrip",
        "tolerance",
        "pytorch_distributed_example_tpu.ops.quant:quantize_blockwise",
        _geoms_codec,
        _run_codec_roundtrip,
    ),
    "prng_stream": Subject(
        "prng_stream",
        "token_exact",
        "pytorch_distributed_example_tpu.serve.engine:ServeEngine.step",
        _geoms_prng,
        _run_prng_stream,
    ),
    "disagg_migration": Subject(
        "disagg_migration",
        "token_exact",
        "pytorch_distributed_example_tpu.serve.disagg.migrate:"
        "migrate_request",
        _geoms_disagg,
        _run_disagg_migration,
    ),
}


def _geom_label(geom: Dict[str, Any]) -> str:
    return " ".join(f"{k}={v}" for k, v in sorted(geom.items()))


def run_sweep(
    quick: bool = False,
    seed_revert: Optional[str] = None,
    only: Optional[str] = None,
    out=sys.stdout,
) -> int:
    """Run the geometry parity sweep; returns the process exit code.

    With ``seed_revert='pr10'`` the ZeRO-update subject is re-run with
    `_perturbed_reduce_scatter_mean` swapped in: every world>1 geometry
    MUST diverge and MUST be localized to a first divergent eqn, or the
    sweeper itself has lost its teeth (exit 1)."""
    _ensure_cpu_jax()
    failures = 0
    total = 0
    for name, subj in SUBJECTS.items():
        if only and name != only:
            continue
        geoms = subj.geometries(quick)
        print(
            f"numlint sweep: subject '{name}' [{subj.tier}] "
            f"contract {subj.contract} ({len(geoms)} geometries)",
            file=out,
        )
        for geom in geoms:
            total += 1
            try:
                res = subj.run(geom)
            except Exception as e:  # a crashed geometry is a failure
                res = {"ok": False, "detail": f"subject crashed: {e!r}"}
            if res["ok"]:
                print(
                    f"  geometry {_geom_label(geom)}: parity OK "
                    f"(hash {res.get('hash', '?')})",
                    file=out,
                )
            else:
                failures += 1
                print(
                    f"  geometry {_geom_label(geom)}: DIVERGED — "
                    f"{res['detail']}",
                    file=out,
                )
    print(
        f"numlint sweep: {total - failures}/{total} geometries "
        "parity-clean",
        file=out,
    )

    rc = 1 if failures else 0
    if seed_revert is None:
        return rc
    if seed_revert != "pr10":
        print(f"unknown seed-revert {seed_revert!r}", file=out)
        return 2

    print(
        "numlint sweep [seed-revert pr10]: perturbing "
        "zero.reduce_scatter_mean (mean division reassociated into the "
        "scatter — the reduction-order class PR 10 forbids)",
        file=out,
    )
    # power-of-two worlds divide exactly, so the reassociated mean is
    # bitwise-identical there — the revert is only OBSERVABLE at
    # non-power-of-two worlds, which is exactly why the matrix carries
    # world=3
    geoms = [
        g for g in SUBJECTS["zero_update"].geometries(quick)
        if g["world"] > 1 and (g["world"] & (g["world"] - 1)) != 0
    ]
    caught = 0
    for geom in geoms:
        res = _run_zero_update(geom, rs_impl=_perturbed_reduce_scatter_mean)
        localized = "first divergent eqn" in res.get("detail", "")
        if not res["ok"] and localized:
            caught += 1
            print(
                f"  geometry {_geom_label(geom)}: DIVERGED (required) — "
                f"{res['detail']}",
                file=out,
            )
        elif not res["ok"]:
            print(
                f"  geometry {_geom_label(geom)}: diverged but NOT "
                f"localized — {res['detail']}",
                file=out,
            )
        else:
            print(
                f"  geometry {_geom_label(geom)}: NOT caught — the "
                "perturbed update passed parity",
                file=out,
            )
    if caught == len(geoms) and geoms:
        print(
            f"seed-revert pr10: caught and localized at {caught}/"
            f"{len(geoms)} eligible geometries — the sweep gate still "
            "has teeth",
            file=out,
        )
        return rc
    print(
        f"seed-revert pr10: only {caught}/{len(geoms)} geometries "
        "caught+localized — the sweeper LOST ITS TEETH",
        file=out,
    )
    return 1


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _maybe_reexec_for_devices(args, quick: bool) -> None:
    """`python -m pytorch_distributed_example_tpu.tools.numlint` imports
    the package — which imports jax — BEFORE main() runs, so setting
    XLA_FLAGS here is too late and the sweep would see one CPU device.
    Re-exec once with the 8-virtual-device environment conftest.py uses;
    in-process callers (tests) already run under that environment and
    never reach this path."""
    if os.environ.get("_TDX_NUMLINT_SWEEP_REEXEC") == "1":
        return
    if "jax" not in sys.modules:
        return  # _ensure_cpu_jax can still set the flags itself
    import jax

    if jax.device_count() >= 8:
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = _harness_xla_flags(env.get("XLA_FLAGS", ""))
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["_TDX_NUMLINT_SWEEP_REEXEC"] = "1"
    cmd = [
        sys.executable,
        "-m",
        "pytorch_distributed_example_tpu.tools.numlint",
        "--sweep",
        "--root",
        args.root,
    ]
    if quick:
        cmd.append("--quick")
    if args.subject:
        cmd += ["--subject", args.subject]
    if args.seed_revert:
        cmd += ["--seed-revert", args.seed_revert]
    os.execve(sys.executable, cmd, env)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="numlint",
        description=(
            "numerics/determinism-plane analyzer (N001-N007) + geometry "
            "parity sweeper"
        ),
    )
    ap.add_argument("--root", default=".", help="project root")
    ap.add_argument(
        "--format", choices=("human", "json", "sarif"), default="human"
    )
    ap.add_argument("--baseline", default=None)
    ap.add_argument("--update-baseline", action="store_true")
    ap.add_argument("--force-baseline-growth", action="store_true")
    ap.add_argument("--show-suppressed", action="store_true")
    ap.add_argument(
        "--sweep", action="store_true",
        help="run the dynamic geometry parity sweep instead of the "
        "static rules",
    )
    ap.add_argument(
        "--quick", action="store_true",
        help="bound the sweep to 2 geometries per subject (also via "
        "TDX_NUMLINT_SWEEP=quick)",
    )
    ap.add_argument(
        "--subject", default=None,
        help="restrict the sweep to one subject",
    )
    ap.add_argument(
        "--seed-revert", default=None, metavar="NAME",
        help="re-run the sweep with a seeded historical revert (pr10: "
        "ZeRO update reduction order) that MUST be caught",
    )
    args = ap.parse_args(argv)

    if args.sweep:
        quick = args.quick or (
            os.environ.get("TDX_NUMLINT_SWEEP", "") == "quick"
        )
        _maybe_reexec_for_devices(args, quick)
        return run_sweep(
            quick=quick, seed_revert=args.seed_revert, only=args.subject
        )

    config = load_config(args.root)
    findings, _project = lint(args.root, config)

    stale_entries: List[Dict] = []
    if args.baseline and os.path.isfile(args.baseline) and not args.update_baseline:
        baseline = load_baseline(args.baseline)
        _new, _matched, stale_entries = apply_baseline(findings, baseline)
    if args.update_baseline:
        path = args.baseline or ".numlint-baseline.json"
        n = write_baseline(
            path,
            findings,
            allow_growth=args.force_baseline_growth,
            tool="numlint",
        )
        print(f"numlint: baseline updated ({n} entries)", file=sys.stderr)

    if args.format == "json":
        print(json.dumps([f.to_dict() for f in findings], indent=2))
    elif args.format == "sarif":
        print(
            json.dumps(
                render_sarif(
                    findings,
                    args.show_suppressed,
                    baseline_mode=bool(args.baseline),
                    tool_name="numlint",
                    rules=RULES,
                    information_uri=_INFO_URI,
                    fingerprint_key="numlint/v1",
                ),
                indent=2,
            )
        )
    else:
        print(render_report(findings, args.show_suppressed, tool="numlint"))
    if stale_entries:
        print(
            f"numlint: {len(stale_entries)} stale baseline entr"
            f"{'y' if len(stale_entries) == 1 else 'ies'} — run "
            "--update-baseline to shrink the ratchet",
            file=sys.stderr,
        )
    active = [
        f
        for f in findings
        if not f.suppressed and not f.baselined and f.severity == "error"
    ]
    return 1 if active else 0


if __name__ == "__main__":
    sys.exit(main())
