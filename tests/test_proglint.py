"""proglint (ISSUE 14) — jaxpr-level program-plane analyzer tests.

Layers:
  * the shared collective collector (recursion through scan/cond/shard_map
    sub-jaxprs — including cond's `branches` TUPLE, which the PR 7
    test-local walker missed);
  * program fingerprints: donation + lowered-aliasing extraction, digest
    stability;
  * rules J001-J004, each with a seeded-regression proof (the acceptance
    scenarios: a donation-dropped decode program for J003, an
    unquantized-payload lowering for J004);
  * the register-on-compile seams (serve/decode, ddp, plan/driver) under
    TDX_PROGLINT=1;
  * the J005 agreement protocol in-process (threads + HashStore,
    mirroring the ScheduleVerifier tests) including the
    `proglint.agree` corrupt chaos seam;
  * the cross-process J005 chaos proof: a real 2-process gang whose
    ranks compile DIVERGENT driver programs (per-rank TDX_PLANNER_FORCE
    skew) and fail at agreement time naming the first divergent
    collective eqn on BOTH ranks, before any collective executes.
"""

import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from pytorch_distributed_example_tpu import faults
from pytorch_distributed_example_tpu.schedule import (
    ProgramScheduleMismatchError,
    agree_program,
)
from pytorch_distributed_example_tpu.store import HashStore, PrefixStore
from pytorch_distributed_example_tpu.tools import proglint
from pytorch_distributed_example_tpu.tools.proglint import (
    CollectiveEqn,
    ProgramFingerprint,
    check_fingerprint,
    collect_collectives,
    expected_perms_from_plan,
    fingerprint_program,
    quantized_wire_violations,
)

from tests._mp_util import REPO, free_port


@pytest.fixture()
def no_fault_plan():
    faults.clear_plan()
    yield
    faults.clear_plan()


@pytest.fixture()
def clean_registry():
    proglint.registry().clear()
    yield proglint.registry()
    proglint.registry().clear()


def _mesh2():
    import jax

    from jax.sharding import Mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    return Mesh(np.array(jax.devices()[:2]), ("dp",))


# ---------------------------------------------------------------------------
# the shared collector
# ---------------------------------------------------------------------------


class TestCollector:
    def test_collects_ordered_eqns_with_axes_shapes_perm(self, world):
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_example_tpu._compat import shard_map_fn

        mesh = _mesh2()

        def body(x):
            x = lax.psum(x, "dp")
            x = lax.ppermute(x, "dp", [(0, 1), (1, 0)])
            y = lax.psum_scatter(x.reshape(-1), "dp", tiled=True)
            return lax.all_gather(y, "dp", tiled=True).reshape(x.shape)

        fn = shard_map_fn(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
        eqns = collect_collectives(
            jax.make_jaxpr(fn)(np.zeros((2, 4), np.float32))
        )
        assert [e.primitive for e in eqns] == [
            "psum", "ppermute", "psum_scatter", "all_gather",
        ]  # reduce_scatter canonicalizes to psum_scatter
        assert [e.index for e in eqns] == [0, 1, 2, 3]
        assert all(e.axes == ("dp",) for e in eqns)
        assert eqns[1].perm == ((0, 1), (1, 0))
        assert eqns[0].operands == (("float32", (1, 4)),)
        assert "perm=0>1;1>0" in eqns[1].descriptor()

    def test_recurses_into_scan_and_cond_branches(self, world):
        """cond carries its sub-jaxprs as a `branches` TUPLE param — the
        container shape the PR 7 test-local walker skipped."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_example_tpu._compat import shard_map_fn

        mesh = _mesh2()

        def body(x):
            def step(carry, _):
                return lax.psum(carry, "dp"), None

            carried, _ = lax.scan(step, x, None, length=2)
            return lax.cond(
                x.sum() > 0,
                lambda v: lax.pmax(v, "dp"),
                lambda v: lax.pmin(v, "dp"),
                carried,
            )

        fn = shard_map_fn(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
        eqns = collect_collectives(
            jax.make_jaxpr(fn)(np.zeros((2, 4), np.float32))
        )
        prims = [e.primitive for e in eqns]
        assert "psum" in prims          # inside the scan body
        assert "pmax" in prims and "pmin" in prims  # both cond branches

    def test_prims_filter(self, world):
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_example_tpu._compat import shard_map_fn

        mesh = _mesh2()

        def body(x):
            return lax.ppermute(
                lax.psum(x, "dp"), "dp", [(0, 1), (1, 0)]
            )

        fn = shard_map_fn(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
        closed = jax.make_jaxpr(fn)(np.zeros((2, 4), np.float32))
        only = collect_collectives(closed, prims=("psum",))
        assert [e.primitive for e in only] == ["psum"]


# ---------------------------------------------------------------------------
# fingerprints: donation + aliasing
# ---------------------------------------------------------------------------


class TestFingerprint:
    def test_donated_and_aliased_extracted(self):
        import functools

        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step(tree, y):
            return {k: v + y for k, v in tree.items()}, y * 2

        x = np.zeros((8,), np.float32)
        fp = fingerprint_program(
            "t.step", step, ({"a": x, "b": x}, x), path="t.py"
        )
        assert fp.donated == (0, 1)
        assert fp.alias_checked
        assert set(fp.donated) <= set(fp.aliased)
        assert not check_fingerprint(fp)

    def test_digest_tracks_collective_sequence(self):
        a = ProgramFingerprint(
            "p",
            eqns=(
                CollectiveEqn(0, "psum", ("dp",), (("float32", (4,)),)),
            ),
        )
        b = ProgramFingerprint(
            "p",
            eqns=(
                CollectiveEqn(0, "psum", ("dp",), (("float32", (8,)),)),
            ),
        )
        assert a.digest != b.digest
        assert a.canonical()["digest"] == a.digest
        assert a.canonical()["eqns"] == [a.eqns[0].descriptor()]

    def test_j003_seeded_donation_dropped_decode_program(self):
        """ACCEPTANCE: a decode-shaped step whose donated rng lane a
        refactor stopped returning — the donation is silently dropped
        at lowering and J003 names the exact argument."""
        import functools

        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def broken_step(tree, lengths, tokens, rngs):
            # rngs is donated but no output reuses its buffer — the
            # "silent 306 ms/step memcpy returns" regression class
            new_tree = {k: v + 1.0 for k, v in tree.items()}
            return new_tree, lengths + 1, tokens

        tree = {"k": np.zeros((4, 8), np.float32),
                "v": np.zeros((4, 8), np.float32)}
        fp = fingerprint_program(
            "serve.broken.step",
            broken_step,
            (
                tree,
                np.zeros((2,), np.int32),
                np.zeros((2,), np.int32),
                np.zeros((2, 2), np.uint32),
            ),
            path="pytorch_distributed_example_tpu/serve/decode.py",
        )
        findings = check_fingerprint(fp)
        j003 = [f for f in findings if f.rule == "J003"]
        assert j003, "dropped donation not caught"
        assert "rngs" in j003[0].message or "flat arg" in j003[0].message
        assert "donation was silently dropped" in j003[0].message

    def test_unused_arg_pruning_does_not_skew_j003(self):
        """jit's keep_unused=False default PRUNES unused args from the
        lowering, shifting its %argN numbering. The alias map must ride
        the kept-var mapping: a donation AFTER an unused arg is neither
        falsely reported dropped nor able to mask a real drop."""
        import functools

        import jax

        @functools.partial(jax.jit, donate_argnums=(1,))
        def kept(unused, x):
            return x + 1.0

        x = np.zeros((8,), np.float32)
        fp = fingerprint_program("t.kept", kept, (np.zeros((3,)), x))
        assert fp.alias_checked
        assert fp.donated == (1,)
        assert fp.aliased == (1,), "pruned numbering leaked into J003"
        assert not [f for f in check_fingerprint(fp) if f.rule == "J003"]

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def dropped(donated_unused, x):
            return x + 1.0

        fp2 = fingerprint_program("t.dropped", dropped, (np.zeros((3,)), x))
        if fp2.alias_checked:
            j003 = [
                f for f in check_fingerprint(fp2) if f.rule == "J003"
            ]
            assert j003, "pruned donated arg's dropped donation missed"

    def test_real_decode_programs_are_donation_clean(self, world):
        """The live paged decode step: every donated leaf aliased."""
        pairs = proglint._serve_programs()
        by_name = {fp.name: fp for fp, _ in pairs}
        step = by_name["serve.paged.step"]
        assert step.donated, "paged step lost its donation set?"
        assert set(step.donated) <= set(step.aliased)
        assert not check_fingerprint(step)


# ---------------------------------------------------------------------------
# rules J001 / J002 / J004
# ---------------------------------------------------------------------------


def _fp_with(eqns, **kw):
    return ProgramFingerprint("p", path="x.py", eqns=tuple(eqns), **kw)


class TestRules:
    def test_j001_unknown_axis_flagged_known_axis_clean(self):
        eq = CollectiveEqn(0, "psum", ("ghost",), (("float32", (4,)),))
        fp = _fp_with([eq], mesh_axes=("dp",))
        bad = check_fingerprint(fp, registry_axes=frozenset({"tp"}))
        assert [f.rule for f in bad] == ["J001"]
        assert "'ghost'" in bad[0].message
        # either the binding mesh or the registry satisfies the rule
        assert not check_fingerprint(
            fp, registry_axes=frozenset({"ghost"})
        )
        ok = _fp_with([eq], mesh_axes=("ghost",))
        assert not check_fingerprint(ok)

    def test_j002_structural_invalid_perms(self):
        dup_src = CollectiveEqn(
            0, "ppermute", ("dp",), (("float32", (4,)),),
            perm=((0, 1), (0, 0)),
        )
        out_of_range = CollectiveEqn(
            1, "ppermute", ("dp",), (("float32", (4,)),),
            perm=((0, 1), (1, 5)),
        )
        fp = _fp_with([dup_src, out_of_range], mesh_axes=("dp",), world=2)
        findings = check_fingerprint(fp)
        assert [f.rule for f in findings] == ["J002", "J002"]
        assert "duplicate sources" in findings[0].message
        assert "outside world 2" in findings[1].message

    def test_j002_plan_artifact_consistency(self):
        """The driver body's ppermute sequence must match the registered
        plan artifact's rounds — divergence names the round."""
        from pytorch_distributed_example_tpu.plan import schedules, topology

        topo = topology.Topology(2, ((0, 1),), "cpu")
        plan = schedules.synthesize("all_reduce", "rhd", 2, 8, topo)
        want = expected_perms_from_plan(plan)
        assert len(want) == 2  # one halving + one doubling round at W=2
        good = [
            CollectiveEqn(
                i, "ppermute", ("dp",), (("float32", (4,)),),
                perm=((0, 1), (1, 0)),
            )
            for i in range(2)
        ]
        fp = _fp_with(good, mesh_axes=("dp",), world=2)
        assert not check_fingerprint(fp, expected_perms=want)
        # a skewed round 2
        bad = list(good)
        bad[1] = CollectiveEqn(
            1, "ppermute", ("dp",), (("float32", (4,)),),
            perm=((0, 0), (1, 1)),
        )
        findings = check_fingerprint(
            _fp_with(bad, mesh_axes=("dp",), world=2), expected_perms=want
        )
        j002 = [f for f in findings if "artifact" in f.message]
        assert j002 and "round 2" in j002[0].message

    def test_j004_seeded_f32_payload_regression(self, world):
        """ACCEPTANCE: quantization dropped from the wire lowering — the
        f32 payload rides the collective and J004 flags it (via the same
        helper tests/test_quant.py pins the real lowering with)."""
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_example_tpu._compat import shard_map_fn

        mesh = _mesh2()

        def broken(x):  # "quantized" all-reduce that forgot to quantize
            return lax.psum(x, "dp")

        fn = shard_map_fn(
            broken, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")
        )
        eqns = collect_collectives(
            jax.make_jaxpr(fn)(np.zeros((2, 512), np.float32))
        )
        viols = quantized_wire_violations(eqns)
        assert viols, "f32 payload regression not caught"
        fp = _fp_with(eqns, mesh_axes=("dp",), world=2)
        findings = check_fingerprint(fp, quantized_wire=True)
        assert [f.rule for f in findings] == ["J004"]
        assert "float32" in findings[0].message

    def test_j004_real_quantized_all_reduce_clean(self, world):
        (fp, meta), = proglint._quant_programs(world)
        assert meta.quantized_wire
        assert not check_fingerprint(fp, quantized_wire=True)
        # int8 payloads present in both phases
        prims = [e.primitive for e in fp.eqns]
        assert "all_to_all" in prims and "all_gather" in prims

    def test_suppression_marks_not_drops(self):
        eq = CollectiveEqn(0, "psum", ("ghost",), (("float32", (4,)),))
        fp = _fp_with([eq])
        findings = check_fingerprint(
            fp, suppress=(("J001", "known synthetic axis"),)
        )
        assert len(findings) == 1 and findings[0].suppressed

    def test_severity_off_and_warning(self):
        eq = CollectiveEqn(0, "psum", ("ghost",), (("float32", (4,)),))
        fp = _fp_with([eq])
        assert not check_fingerprint(fp, severity={"J001": "off"})
        warn = check_fingerprint(fp, severity={"J001": "warning"})
        assert warn and warn[0].severity == "warning"


# ---------------------------------------------------------------------------
# register-on-compile seams
# ---------------------------------------------------------------------------


class TestInstrumentation:
    def test_off_by_default_returns_same_object(self, monkeypatch):
        monkeypatch.delenv("TDX_PROGLINT", raising=False)
        import jax

        f = jax.jit(lambda x: x + 1)
        assert proglint.instrument("t", f) is f

    def test_armed_registers_once(self, monkeypatch, clean_registry):
        monkeypatch.setenv("TDX_PROGLINT", "1")
        import jax

        f = jax.jit(lambda x: x * 2)
        w = proglint.instrument("t.prog", f, path="t.py")
        assert w is not f
        x = np.zeros((4,), np.float32)
        np.testing.assert_array_equal(np.asarray(w(x)), x * 2)
        w(x)
        entries = clean_registry.entries()
        assert [(n, o) for n, o, _ in entries] == [("t.prog", 0)]
        assert entries[0][2].path == "t.py"

    def test_serve_seam_registers_under_env(
        self, monkeypatch, clean_registry, world
    ):
        monkeypatch.setenv("TDX_PROGLINT", "1")
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import (
            TransformerConfig,
            TransformerLM,
        )
        from pytorch_distributed_example_tpu.serve import decode

        # a config distinct from every other test's so the lru_cache
        # cannot hand back a pre-armed (unwrapped) program quadruple
        cfg = TransformerConfig(
            vocab_size=16, d_model=8, n_layers=1, n_heads=2,
            max_seq_len=8, use_flash=False,
        )
        model = TransformerLM(cfg)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )["params"]
        from pytorch_distributed_example_tpu.serve import PagedKVCache

        prefill_chunk, _first, _attach, _step = decode.paged_programs(
            model, 0.0, None
        )
        assert hasattr(prefill_chunk, "_proglint_wrapped")
        pool = PagedKVCache(model, slots=1, num_blocks=2, block_size=4)
        pool.allocate()
        assert pool.ensure_blocks(0, 3)
        prefill_chunk(
            params, pool.tree, jnp.zeros((1, 4), jnp.int32),
            pool.tables(slice(0, 1)), 0,
        )
        names = [n for n, _, _ in clean_registry.entries()]
        assert names == ["serve.paged.prefill_chunk"]
        fp = clean_registry.get("serve.paged.prefill_chunk")[0]
        assert fp.path.endswith("serve/decode.py")

    def test_plan_seam_registers_and_reregisters_ordinal(
        self, monkeypatch, clean_registry, world
    ):
        monkeypatch.setenv("TDX_PROGLINT", "1")
        from pytorch_distributed_example_tpu.plan import driver

        mesh = _mesh2()
        x = np.zeros((2, 8), np.float32)
        p1 = driver.compiled_body("all_reduce", "rhd", 2, "dp", mesh)
        p1(x)
        p2 = driver.compiled_body("all_reduce", "rhd", 2, "dp", mesh)
        p2(x)
        entries = clean_registry.entries()
        assert [(n, o) for n, o, _ in entries] == [
            ("plan.all_reduce.rhd", 0),
            ("plan.all_reduce.rhd", 1),
        ]
        assert entries[0][2].digest == entries[1][2].digest
        assert [e.primitive for e in entries[0][2].eqns] == [
            "ppermute", "ppermute",
        ]


# ---------------------------------------------------------------------------
# J005: the agreement protocol (in-process)
# ---------------------------------------------------------------------------


def _payload(eqns, digest=None):
    fp = ProgramFingerprint("prog", eqns=tuple(eqns))
    doc = fp.canonical()
    if digest is not None:
        doc["digest"] = digest
    return doc


def _run_ranks(fns, timeout=30.0):
    errs = [None] * len(fns)

    def call(i):
        try:
            fns[i]()
        except Exception as e:  # noqa: BLE001 - recorded for assertions
            errs[i] = e

    ts = [threading.Thread(target=call, args=(i,)) for i in range(len(fns))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout)
    return errs


_EQ_A = CollectiveEqn(0, "psum_scatter", ("dp",), (("float32", (64,)),))
_EQ_B = CollectiveEqn(0, "ppermute", ("dp",), (("float32", (64,)),),
                      perm=((0, 1), (1, 0)))


class TestAgreementProtocol:
    def test_identical_programs_agree(self, no_fault_plan):
        store = HashStore(timeout=10.0)
        pre = PrefixStore("proglint", store)
        errs = _run_ranks(
            [
                lambda r=r: agree_program(
                    pre, r, 2, "prog#0", _payload([_EQ_A]), timeout=5.0
                )
                for r in range(2)
            ]
        )
        assert errs == [None, None]

    def test_divergent_eqn_named_on_both_ranks(self, no_fault_plan):
        store = HashStore(timeout=10.0)
        pre = PrefixStore("proglint", store)
        payloads = [_payload([_EQ_A]), _payload([_EQ_B])]
        errs = _run_ranks(
            [
                lambda r=r: agree_program(
                    pre, r, 2, "prog#0", payloads[r], timeout=5.0
                )
                for r in range(2)
            ]
        )
        for e in errs:
            assert isinstance(e, ProgramScheduleMismatchError)
        msg = str(errs[0])
        assert "#1" in msg
        assert "psum_scatter" in msg and "ppermute" in msg
        assert "BEFORE any collective executed" in msg

    def test_missing_rank_times_out_into_diagnostic(self, no_fault_plan):
        store = HashStore(timeout=10.0)
        pre = PrefixStore("proglint", store)
        errs = _run_ranks(
            [
                lambda: agree_program(
                    pre, 0, 2, "prog#0", _payload([_EQ_A]), timeout=0.5
                )
            ]
        )
        assert isinstance(errs[0], ProgramScheduleMismatchError)
        assert "rank(s) [1]" in str(errs[0])
        assert "never published" in str(errs[0])

    def test_corrupt_fault_raises_on_every_rank(self):
        """SATELLITE chaos proof: a corrupt published fingerprint raises
        ProgramScheduleMismatchError on EVERY rank instead of hanging in
        first dispatch."""
        faults.clear_plan()
        faults.install_plan(
            [
                {
                    "point": "proglint.agree",
                    "rank": 1,
                    "action": "corrupt",
                }
            ],
            export_env=False,
        )
        try:
            store = HashStore(timeout=10.0)
            pre = PrefixStore("proglint", store)
            errs = _run_ranks(
                [
                    lambda r=r: agree_program(
                        pre, r, 2, "prog#0", _payload([_EQ_A]),
                        timeout=5.0,
                    )
                    for r in range(2)
                ]
            )
            for e in errs:
                assert isinstance(e, ProgramScheduleMismatchError), errs
        finally:
            faults.clear_plan()

    def test_length_mismatch_names_extra_eqn(self, no_fault_plan):
        store = HashStore(timeout=10.0)
        pre = PrefixStore("proglint", store)
        payloads = [_payload([_EQ_A]), _payload([_EQ_A, _EQ_B])]
        errs = _run_ranks(
            [
                lambda r=r: agree_program(
                    pre, r, 2, "prog#0", payloads[r], timeout=5.0
                )
                for r in range(2)
            ]
        )
        for e in errs:
            assert isinstance(e, ProgramScheduleMismatchError)
        assert "1 collective eqn(s)" in str(errs[0])
        assert "ppermute" in str(errs[0])


# ---------------------------------------------------------------------------
# J005: the cross-process chaos proof (TDX_PLANNER_FORCE skew)
# ---------------------------------------------------------------------------

_GANG_WORKER = """
import os, sys
sys.path.insert(0, {repo!r})
rank = int(os.environ["RANK"])
jport, sport = (int(a) for a in sys.argv[1:3])

import jax
jax.config.update("jax_platforms", "cpu")
# 2 LOCAL cpu devices per process (the spawning test pins XLA_FLAGS;
# jax 0.4.x has no jax_num_cpu_devices config)
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{{jport}}",
    num_processes=2,
    process_id=rank,
)

import numpy as np
import pytorch_distributed_example_tpu as tdx
from pytorch_distributed_example_tpu.schedule import (
    ProgramScheduleMismatchError,
)
from pytorch_distributed_example_tpu.plan import driver

# fake backend: real multiproc process group (store, ranks, agreement
# plumbing) without cross-process device collectives — the program under
# test compiles over each rank's LOCAL 2-device mesh, exactly the
# "every rank compiles its own SPMD program" shape trace-time planner
# choices produce
pg = tdx.init_process_group(
    backend="fake",
    init_method=f"tcp://127.0.0.1:{{sport}}",
    rank=rank,
    world_size=2,
)
# the trace-time planner-choice skew ROADMAP item 4 worries about: each
# rank compiles the schedule its own (forced) probe table picked
alg = os.environ["TDX_PLANNER_FORCE"]
mesh = jax.sharding.Mesh(np.array(jax.local_devices()[:2]), ("dp",))
prog = driver.compiled_body("all_reduce", alg, 2, "dp", mesh)
rc = 0
try:
    # first call: register-on-compile fingerprints + agrees BEFORE the
    # program dispatches anything
    prog(np.zeros((2, 64), np.float32))
    print(f"RAN {{rank}}")
except ProgramScheduleMismatchError as e:
    print(f"MISMATCH {{rank}} {{e}}")
    rc = 7
sys.exit(rc)
"""


@pytest.fixture()
def _gang(tmp_path):
    def launch(skew, timeout):
        script = tmp_path / "worker.py"
        script.write_text(textwrap.dedent(_GANG_WORKER.format(repo=REPO)))
        jport, sport = free_port(), free_port()
        procs = []
        for rank in range(2):
            env = dict(os.environ)
            env.update(
                {
                    "RANK": str(rank),
                    "TDX_PROGLINT": "1",
                    "TDX_PROGLINT_TIMEOUT_S": "30",
                    "TDX_PLANNER_FORCE": skew[rank],
                    "XLA_FLAGS": (
                        "--xla_force_host_platform_device_count=2"
                    ),
                    "PYTHONPATH": REPO
                    + os.pathsep
                    + env.get("PYTHONPATH", ""),
                }
            )
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(script), str(jport), str(sport)],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                    env=env,
                )
            )
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                return procs, None
            outs.append(out.decode())
        return procs, outs

    def run(skew, timeout=120):
        # `free_port` closes the socket it found the port with, and under
        # `-n 6` another worker's test can take the port before this gang
        # binds it: the gang then hangs or a rank dies outside the worker's
        # own exit codes (0 ran, 7 mismatch). One more try on fresh ports.
        for attempt in range(2):
            procs, outs = launch(skew, timeout)
            if outs is not None and all(p.returncode in (0, 7) for p in procs):
                break
        if outs is None:
            pytest.fail(f"proglint gang hung (skew={skew})")
        return procs, outs

    return run


class TestCrossProcessAgreement:
    """ACCEPTANCE: divergent compiled programs (per-rank
    TDX_PLANNER_FORCE skew) fail at agreement time on BOTH ranks,
    naming the first divergent collective eqn, before any collective
    executes."""

    def test_skewed_planner_force_fails_agreement_on_both_ranks(
        self, _gang
    ):
        procs, outs = _gang(("ring", "rhd"))
        for r, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 7, out
            assert f"MISMATCH {r}" in out
            # the first divergent eqn is NAMED: ring leads with a
            # psum_scatter, rhd with a ppermute
            assert "#1" in out
            assert "psum_scatter" in out and "ppermute" in out
            assert "RAN" not in out  # failed BEFORE the program ran

    def test_agreeing_ranks_run(self, _gang):
        procs, outs = _gang(("rhd", "rhd"))
        for r, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, out
            assert f"RAN {r}" in out
