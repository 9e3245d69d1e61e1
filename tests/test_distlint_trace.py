"""distlint v3: trace-context reachability, donation flow, pool/lock/spec
rules (R011-R015) — fixture-corpus acceptance shapes plus real-repo graph
facts — and the `TDX_TRACE_GUARD` runtime complement.

The corpus under tests/fixtures/distlint_interproc carries the
DELIBERATE findings (excluded from the self-lint scan); the real-repo
assertions pin the model facts the rules ride on: the decode program
factory's jitted bodies are trace roots, the planner's algorithm bodies
are configured roots, the ZeRO/decode donation sets are harvested, and
the mesh-axis registry holds the axes the repo actually constructs."""

import os

import pytest

from pytorch_distributed_example_tpu.tools.distlint import (
    LintConfig,
    build_project,
    lint_paths,
    load_config,
)
from pytorch_distributed_example_tpu.traceguard import TraceGuardError

from tests._mp_util import REPO

FIXTURE = os.path.join("tests", "fixtures", "distlint_interproc")
_CFG = LintConfig(paths=[FIXTURE])

_MEMO: dict = {}


def _fixture_findings():
    if "findings" not in _MEMO:
        _MEMO["findings"] = lint_paths([FIXTURE], root=REPO, config=_CFG)
    return _MEMO["findings"]


def _package_project():
    if "package" not in _MEMO:
        _MEMO["package"] = build_project(
            ["pytorch_distributed_example_tpu"],
            root=REPO,
            config=load_config(REPO),
        )
    return _MEMO["package"]


def _rule(rule, path_tail):
    return [
        f
        for f in _fixture_findings()
        if f.rule == rule and f.path.endswith(path_tail)
    ]


class TestR011TraceReach:
    def test_two_hop_host_effect_flagged_with_trace(self):
        """THE acceptance fixture: a jit-decorated body reaching
        `device_get` through two helper hops, caller→callee trace in the
        report."""
        fs = [f for f in _rule("R011", "traced.py") if f.line == 17]
        assert len(fs) == 1
        f = fs[0]
        assert not f.suppressed
        assert "measure_and_probe" in f.message
        assert "device_get" in f.message
        assert "trace root" in f.message
        assert list(f.trace) == [
            "traced.train_step",
            "hostops.measure_and_probe",
            "hostops.probe_readback",
        ]

    def test_direct_fire_and_store_under_trace_flagged(self):
        msgs = [f.message for f in _rule("R011", "traced.py")]
        assert any("faults.fire" in m for m in msgs)
        assert any("store.wait" in m for m in msgs)

    def test_eager_caller_of_same_helper_is_clean(self):
        # eager_probe calls the identical helper with no trace root above
        assert not [f for f in _rule("R011", "traced.py") if f.line >= 36]

    def test_reachable_helper_fns_flagged_at_their_sites(self):
        fs = _rule("R011", "hostops.py")
        assert fs, "trace-reachable helpers must be flagged too"
        assert all("traced.train_step" in f.message for f in fs)

    def test_pr10_planner_hook_shape_regression(self):
        """The documented PR 10 bug shape: a jitted step whose chooser
        probes (store agreement + device readback of a tracer) at trace
        time. The real plan.ddp_comm_hook declines in multiproc mode to
        avoid this; the lint must keep catching the shape."""
        fs = _rule("R011", "planner_hook.py")
        assert fs
        msgs = " | ".join(f.message for f in fs)
        assert "device_get" in msgs
        assert "store.get" in msgs
        step_site = [
            f for f in fs if "choose_algorithm" in f.message
            and "train_step_with_hook" in f.message
        ]
        assert step_site, [f.render() for f in fs]


class TestR012Donation:
    def test_use_after_donate_flagged(self):
        fs = _rule("R012", "donate.py")
        lines = {f.line for f in fs}
        assert 32 in lines  # state.sum() after step(state, ...)
        assert 43 in lines  # `a` read after pair_step(a, b)
        assert 54 in lines  # through the wrapper escape summary
        assert 60 in lines  # through the locally-built jit donator

    def test_rebind_and_tuple_unpack_idioms_clean(self):
        fs = _rule("R012", "donate.py")
        # good_rebind (loop) spans lines 25-28; good_tuple_unpack 37-39
        assert not [f for f in fs if f.line < 31]
        assert not [f for f in fs if 37 <= f.line <= 39]

    def test_wrapper_escape_summary_computed(self):
        proj = _MEMO.get("fixture_proj")
        if proj is None:
            proj = _MEMO["fixture_proj"] = build_project(
                [FIXTURE], root=REPO, config=_CFG
            )
        mod = proj.modules["tests.fixtures.distlint_interproc.donate"]
        assert mod.functions["step"].donates == {0}
        assert mod.functions["pair_step"].donates == {0, 1}
        assert mod.functions["wrapper"].donates_params == {0}


class TestR013PoolPairing:
    def test_leak_via_early_return_flagged(self):
        fs = _rule("R013", "pool.py")
        lines = {f.line for f in fs}
        assert 13 in lines  # leak_on_early_return
        assert 51 in lines  # leak_ensure_local

    def test_clean_shapes_stay_clean(self):
        fs = _rule("R013", "pool.py")
        assert {f.line for f in fs} == {13, 51}, [f.render() for f in fs]


class TestR013TryFinally:
    def test_try_finally_release_idiom_is_clean(self):
        """`finally` runs on every exit path — the canonical
        acquire/try/return/finally-free shape must not flag."""
        import textwrap

        from pytorch_distributed_example_tpu.tools.distlint import (
            lint_source,
        )

        src = textwrap.dedent(
            """
            def run_with_blocks(pool, req):
                b = pool.allocate()
                try:
                    return req.run(b)
                finally:
                    pool.free(b)
            """
        )
        assert not [f for f in lint_source(src, "x.py") if f.rule == "R013"]


class TestR012BoundMethods:
    def test_use_after_donate_through_jitted_method_flagged(self):
        """donate_argnums on a method counts `self`; the bound call site
        does not — the index must shift or method code escapes the rule."""
        import textwrap

        from pytorch_distributed_example_tpu.tools.distlint import (
            lint_source,
        )

        src = textwrap.dedent(
            """
            import functools
            import jax


            class Runner:
                @functools.partial(jax.jit, donate_argnums=(1,))
                def step(self, state):
                    return state + 1

                def drive(self, state):
                    out = self.step(state)
                    return out, state.sum()  # use-after-donate
            """
        )
        fs = [f for f in lint_source(src, "x.py") if f.rule == "R012"]
        assert len(fs) == 1
        assert "`state`" in fs[0].message


class TestR014LockDiscipline:
    def test_unlocked_write_of_guarded_field_flagged(self):
        fs = _rule("R014", "locks.py")
        assert len(fs) == 1
        assert "self.hits" in fs[0].message
        assert fs[0].line == 22

    def test_lockless_class_out_of_scope(self):
        assert not [
            f for f in _rule("R014", "locks.py") if "count" in f.message
        ]


class TestR015SpecDrift:
    def test_unknown_axis_flagged_known_axes_clean(self):
        fs = _rule("R015", "specs.py")
        assert len(fs) == 1
        assert "`model`" in fs[0].message
        assert "'dp'" in fs[0].message and "'tp'" in fs[0].message


class TestRealRepoGraph:
    def test_decode_program_factory_bodies_are_trace_roots(self):
        proj = _package_project()
        mod = proj.modules["pytorch_distributed_example_tpu.serve.decode"]
        for name in (
            "paged_programs.<locals>.step",
            "paged_programs.<locals>.prefill_chunk",
        ):
            fi = mod.functions[name]
            assert fi.trace_root is not None
            assert fi.trace_ctx is not None

    def test_decode_step_donation_sets_harvested(self):
        proj = _package_project()
        mod = proj.modules["pytorch_distributed_example_tpu.serve.decode"]
        fns = mod.functions
        assert fns["paged_programs.<locals>.step"].donates == {1, 2, 3, 4}
        assert fns["paged_programs.<locals>.prefill_chunk"].donates == {1}
        assert fns["paged_programs.<locals>.attach"].donates == {0, 1, 2}

    def test_planner_bodies_are_configured_trace_roots(self):
        proj = _package_project()
        mod = proj.modules["pytorch_distributed_example_tpu.plan.driver"]
        fi = mod.functions["body_for.<locals>.ring"]
        assert fi.trace_root is not None
        assert "configured" in fi.trace_root

    def test_ddp_local_step_is_trace_root_via_shard_map(self):
        proj = _package_project()
        mod = proj.modules["pytorch_distributed_example_tpu.parallel.ddp"]
        fi = mod.functions["make_ddp_train_step.<locals>.local_step"]
        assert fi.trace_root is not None

    def test_fsdp_step_is_trace_root_through_the_rung_decorator(self):
        """`jax.jit(trace(step), ...)`: the trainers hand their step to
        `utils.remat.fitted` through a decorator applied by hand."""
        proj = _package_project()
        mod = proj.modules["pytorch_distributed_example_tpu.parallel.fsdp"]
        fi = mod.functions["_make_constrained_train_step.<locals>.step"]
        assert fi.trace_root is not None

    def test_a_function_wrapped_by_hand_keeps_its_host_effects_policed(self):
        import textwrap

        from pytorch_distributed_example_tpu.tools.distlint import (
            lint_source,
        )

        src = textwrap.dedent(
            """
            import jax


            def build(trace):
                def step(x):
                    return x + x.sum().item()  # a host effect inside a traced body

                return jax.jit(trace(step), donate_argnums=(0,))
            """
        )
        bare = src.replace("trace(step)", "step")
        found = lambda text: sorted(
            (f.rule, f.line) for f in lint_source(text, "x.py")
        )
        assert found(src) == found(bare) and found(bare)

    def test_mesh_axis_registry_holds_repo_axes(self):
        # the package itself constructs `dp` meshes (TP/serve meshes are
        # caller-provided and harvested from tests/examples in the full
        # self-gate scan)
        proj = _package_project()
        assert "dp" in proj.mesh_axes


class TestSarifCliNewRules:
    def test_sarif_carries_new_rule_ids_with_fingerprints(self):
        """CLI gate for R011-R015: lint the fixture corpus (where the
        deliberate findings live) as a subprocess in SARIF mode and
        check every new rule surfaces as a result with the
        partialFingerprint the baseline ratchet keys on."""
        import json
        import subprocess
        import sys

        out = subprocess.run(
            [
                sys.executable,
                "-m",
                "pytorch_distributed_example_tpu.tools.distlint",
                "--no-config",
                "--format",
                "sarif",
                FIXTURE,
            ],
            capture_output=True,
            text=True,
            cwd=REPO,
        )
        assert out.returncode == 1, out.stdout + out.stderr  # deliberate findings
        doc = json.loads(out.stdout)
        results = doc["runs"][0]["results"]
        by_rule = {r["ruleId"] for r in results}
        assert {"R011", "R012", "R013", "R014", "R015"} <= by_rule
        for r in results:
            assert r["partialFingerprints"]["distlint/v1"]
        rules = {r["id"] for r in doc["runs"][0]["tool"]["driver"]["rules"]}
        assert {f"R{i:03d}" for i in range(1, 16)} <= rules


class TestTraceGuard:
    def test_store_wait_under_jit_tracing_raises_named(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.store import HashStore

        monkeypatch.setenv("TDX_TRACE_GUARD", "1")
        st = HashStore()
        st.set("ready", b"1")

        def body(x):
            st.wait(["ready"])
            return x + 1

        with pytest.raises(TraceGuardError) as ei:
            jax.jit(body)(jnp.zeros(()))
        assert "store.wait" in str(ei.value)

    def test_hashstore_get_under_tracing_raises(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.store import HashStore

        monkeypatch.setenv("TDX_TRACE_GUARD", "1")
        st = HashStore()
        st.set("k", b"1")

        def body(x):
            st.get("k")
            return x * 2

        with pytest.raises(TraceGuardError) as ei:
            jax.jit(body)(jnp.zeros(()))
        assert "store.get" in str(ei.value)

    def test_faults_fire_under_tracing_raises(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu import faults

        monkeypatch.setenv("TDX_TRACE_GUARD", "1")

        def body(x):
            faults.fire("train.step")  # distlint: disable=R011 -- deliberate: proves the TDX_TRACE_GUARD runtime half catches exactly what R011 flags statically
            return x - 1

        with pytest.raises(TraceGuardError) as ei:
            jax.jit(body)(jnp.zeros(()))
        assert "train.step" in str(ei.value)

    def test_inert_when_unset(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.store import HashStore

        monkeypatch.delenv("TDX_TRACE_GUARD", raising=False)
        st = HashStore()
        st.set("ready", b"1")

        def body(x):
            st.wait(["ready"])  # key exists: trace-time wait returns
            return x + 1

        assert float(jax.jit(body)(jnp.zeros(()))) == 1.0

    def test_eager_ops_pass_with_guard_armed(self, monkeypatch):
        from pytorch_distributed_example_tpu.store import HashStore

        monkeypatch.setenv("TDX_TRACE_GUARD", "1")
        st = HashStore()
        st.set("k", b"v")
        assert st.get("k") == b"v"  # outside any trace: untouched


class TestZeroDonationContract:
    def test_sharded_opt_state_cannot_reenter_donation(self):
        from pytorch_distributed_example_tpu.parallel import zero

        # the PR 10 repro is a lint error + this named failure now
        with pytest.raises(ValueError, match="donate_argnums"):
            zero.assert_donation_contract(
                (0, 1, 2), sharded_opt_state=True
            )

    def test_valid_sets_pass_through(self):
        from pytorch_distributed_example_tpu.parallel import zero

        assert zero.assert_donation_contract(
            (0, 2), sharded_opt_state=True
        ) == (0, 2)
        assert zero.assert_donation_contract(
            (0, 1, 2), sharded_opt_state=False
        ) == (0, 1, 2)
