"""proglint self-gate: the program-plane analyzer over the repo's OWN
registered compiled programs, ratcheted by `.proglint-baseline.json` and
drift-gated by the golden fingerprint corpus — the tier-1 contract
mirroring `tests/test_distlint_self.py`:

  * zero unsuppressed error findings over every registered program
    (serve decode slot/paged, DDP replicated + ZeRO train steps, plan
    driver bodies, quantized_all_reduce) — at the SESSION geometry here
    (8 virtual devices) and at the CLI's 2-device geometry in the
    subprocess gate;
  * the exact ISSUE CLI (`--format sarif --baseline
    .proglint-baseline.json`) exits 0 with structurally-valid SARIF
    2.1.0 carrying proglint/v1 partialFingerprints, plus the golden
    corpus gate (`--corpus`): a donation-set or collective-sequence
    change without a corpus update fails tier-1;
  * J001 consumes distlint's harvested mesh-axis registry — ONE source
    of truth across the source plane (R015) and the program plane.
"""

import json
import os
import subprocess
import sys

import pytest

from pytorch_distributed_example_tpu.tools import proglint
from pytorch_distributed_example_tpu.tools.distlint import (
    harvested_mesh_axes,
)
from pytorch_distributed_example_tpu.tools.proglint import (
    CORPUS_PROGRAMS,
    CollectiveEqn,
    ProgramFingerprint,
    check_fingerprint,
    corpus_diff,
    lint_repo_programs,
    load_config,
)

from tests._mp_util import REPO

BASELINE = os.path.join(REPO, ".proglint-baseline.json")
CORPUS_DIR = os.path.join(REPO, "tests", "fixtures", "proglint")


_CACHE = []


def _pairs(world):
    """One build per test session (traces + two tiny ddp steps). The
    planner's state outlives a test: the session's group keeps a
    `planner_override` and the agreed table is process-global, so a planner
    test that ran earlier in this worker can leave the ZeRO step tracing
    ring `ppermute`s where its fingerprint expects `psum_scatter` /
    `all_gather` (ROADMAP D0: which files share a worker is the
    scheduler's choice). Build from the defaults."""
    if not _CACHE:
        from pytorch_distributed_example_tpu import plan

        plan.enable_for_group(world, None)  # defer to the environment: off
        plan.traced.reset()
        _CACHE.append(proglint.build_repo_programs())
    return _CACHE[0]


class TestRepoProgramsClean:
    def test_zero_unsuppressed_findings(self, world):
        findings = lint_repo_programs(REPO, _pairs(world))
        active = [
            f for f in findings if not f.suppressed and f.severity == "error"
        ]
        assert not active, "\n".join(f.render() for f in active)

    def test_catalog_covers_the_registered_surfaces(self, world):
        names = {fp.name for fp, _ in _pairs(world)}
        assert {
            "serve.paged.step",
            "serve.paged.prefill_chunk",
            "ddp.train_step.zero",
            "ddp.train_step.replicated",
            "plan.all_reduce.ring",
            "plan.all_reduce.rhd",
            "plan.all_gather.ring",
            "plan.reduce_scatter.ring",
            "ops.quantized_all_reduce",
        } <= names

    def test_zero_step_fingerprint_shape(self, world):
        """The ZeRO step IS the program class proglint was built for:
        psum_scatter halves + all_gather halves, donated params, the
        sharded opt state NOT donated (the PR 10 contract)."""
        by_name = {fp.name: fp for fp, _ in _pairs(world)}
        fp = by_name["ddp.train_step.zero"]
        prims = [e.primitive for e in fp.eqns]
        assert "psum_scatter" in prims and "all_gather" in prims
        assert fp.donated, "ZeRO step lost its donation set"
        assert set(fp.donated) <= set(fp.aliased)


class TestBaselineAndCorpusFiles:
    def test_baseline_is_committed_and_empty(self):
        with open(BASELINE, encoding="utf-8") as fh:
            doc = json.load(fh)
        assert doc["tool"] == "proglint"
        assert doc["findings"] == [], (
            "the proglint ratchet starts (and must stay) at zero — fix "
            "or suppress findings instead of baselining them"
        )

    def test_corpus_files_exist(self):
        for name in CORPUS_PROGRAMS:
            fn = os.path.join(CORPUS_DIR, name + ".json")
            assert os.path.isfile(fn), f"missing golden corpus entry {fn}"
            with open(fn, encoding="utf-8") as fh:
                doc = json.load(fh)
            assert doc["name"] == name
            assert doc["digest"]
            assert isinstance(doc["eqns"], list)

    def test_corpus_diff_catches_seeded_drift(self, tmp_path, world):
        """The ratchet machinery itself: a changed collective sequence
        or donation set against the committed corpus is reported."""
        fp = ProgramFingerprint(
            "ddp.train_step.zero",
            eqns=(
                CollectiveEqn(
                    0, "psum", ("_ranks",), (("float32", (4,)),)
                ),
            ),
            donated=(0,),
            aliased=(0,),
        )
        problems = corpus_diff([(fp, proglint.ProgramMeta())], CORPUS_DIR)
        assert problems
        assert any("eqns drifted" in p for p in problems)

    def test_corpus_diff_clean_on_identical(self, tmp_path):
        from pytorch_distributed_example_tpu.tools.proglint import (
            write_corpus,
        )

        fp = ProgramFingerprint(
            "x.prog",
            eqns=(
                CollectiveEqn(0, "psum", ("dp",), (("float32", (4,)),)),
            ),
        )
        pairs = [(fp, proglint.ProgramMeta())]
        write_corpus(pairs, str(tmp_path))
        assert corpus_diff(pairs, str(tmp_path)) == []
        missing = corpus_diff(
            [
                (
                    ProgramFingerprint("y.prog"),
                    proglint.ProgramMeta(),
                )
            ],
            str(tmp_path),
        )
        assert missing and "no golden corpus entry" in missing[0]


class TestCrossToolMeshAxisRegistry:
    """SATELLITE: one mesh-axis source of truth. distlint R015 harvests
    it; proglint J001 consumes the export instead of re-harvesting."""

    def test_harvest_contains_the_live_axes(self):
        axes = harvested_mesh_axes(REPO)
        # the backend's flattened axis + the mesh axes repo programs use
        assert {"_ranks", "dp", "tp"} <= set(axes)

    def test_j001_is_fed_by_the_distlint_harvest(self):
        axes = harvested_mesh_axes(REPO)
        eq = CollectiveEqn(0, "psum", ("_ranks",), (("float32", (4,)),))
        fp = ProgramFingerprint("x", eqns=(eq,))  # no binding mesh info
        # the harvest alone clears it; without the harvest it fails
        assert not check_fingerprint(fp, registry_axes=axes)
        assert [
            f.rule for f in check_fingerprint(fp)
        ] == ["J001"]


class TestSarifCliGate:
    """The exact CLI from the ISSUE, as a subprocess, with the golden
    corpus gate riding along: exit 0, valid SARIF 2.1.0, proglint/v1
    partialFingerprints, zero unsuppressed, zero corpus drift."""

    @pytest.fixture(scope="class")
    def cli(self):
        out = subprocess.run(
            [
                sys.executable,
                "-m",
                "pytorch_distributed_example_tpu.tools.proglint",
                "--format",
                "sarif",
                "--baseline",
                ".proglint-baseline.json",
                "--corpus",
            ],
            capture_output=True,
            text=True,
            cwd=REPO,
            timeout=600,
        )
        return out

    def test_exit_zero(self, cli):
        assert cli.returncode == 0, cli.stdout + cli.stderr

    def test_sarif_shape(self, cli):
        doc = json.loads(cli.stdout)
        assert doc["version"] == "2.1.0"
        driver = doc["runs"][0]["tool"]["driver"]
        assert driver["name"] == "proglint"
        rules = {r["id"] for r in driver["rules"]}
        assert {f"J{i:03d}" for i in range(1, 6)} <= rules
        for r in doc["runs"][0]["results"]:
            assert r["partialFingerprints"]["proglint/v1"]
        # at a clean ratchet nothing may be "new"
        assert not [
            r
            for r in doc["runs"][0]["results"]
            if r.get("baselineState") == "new"
        ]

    def test_no_corpus_drift(self, cli):
        assert "corpus drift" not in cli.stderr, cli.stderr


def test_config_loads():
    cfg = load_config(REPO)
    assert cfg.corpus == "tests/fixtures/proglint"
