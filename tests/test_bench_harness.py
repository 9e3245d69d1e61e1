"""Contracts of what is left of the bench harness (bench.py,
benchmarks/common.py, benchmarks/run_all.py) after the bring-up PR: it
runs on the chip or exits nonzero, peaks come from one table, rows are
merged atomically into the directory a chip run brings back, and the
timing barrier waits and lets errors through."""

import importlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def bench_mod():
    sys.path.insert(0, ROOT)
    try:
        yield importlib.import_module("bench")
    finally:
        sys.path.remove(ROOT)


@pytest.fixture()
def common():
    sys.path.insert(0, ROOT)
    try:
        yield importlib.import_module("benchmarks.common")
    finally:
        sys.path.remove(ROOT)


class TestBenchRunsOnTheChipOrNotAtAll:
    def test_no_tpu_exits_nonzero_naming_the_platform(self, bench_mod):
        with pytest.raises(SystemExit) as exc:
            bench_mod.main()
        assert exc.value.code not in (0, None)
        assert "no TPU" in str(exc.value.code)
        assert "'cpu'" in str(exc.value.code)

    def test_known_kinds_resolve_from_the_table(self, bench_mod):
        assert bench_mod._peak_flops("TPU v5 lite") == 197e12
        assert bench_mod._peak_flops("TPU v4") == 275e12

    def test_unknown_device_kind_is_an_error(self, bench_mod):
        with pytest.raises(ValueError, match="no bf16 peak on record"):
            bench_mod._peak_flops("cpu")
        with pytest.raises(ValueError, match="TPU v9"):
            bench_mod._peak_flops("TPU v9")

    def test_fallback_machinery_is_gone(self, bench_mod):
        for name in (
            "_pin_cpu", "_acquire_jax", "_probe_backend_subprocess",
            "_WedgeWatchdog", "_persist_tpu_result", "_calibrated_peak",
            "_committed_tpu_rows", "_commit_subject", "_tick",
        ):
            assert not hasattr(bench_mod, name), name
        with open(os.path.join(ROOT, "bench.py")) as f:
            src = f.read()
        for knob in ("BENCH_PLATFORM", "BENCH_WINDOW_S", "BENCH_POLL_S",
                     "BENCH_PROBE_TIMEOUT", "BENCH_WEDGE_BUDGET",
                     "BENCH_AUTOCOMMIT", "BENCH_HEADLINE_KEY",
                     "TDX_CPU_PERF_FLAGS", "subprocess"):
            assert knob not in src, knob

    def test_steady_rate_is_a_true_median_of_the_tail(self, bench_mod):
        assert bench_mod._steady_rate([5.0]) == 5.0
        assert bench_mod._steady_rate([1.0, 9.0, 7.0, 8.0]) == 8.0
        assert bench_mod._steady_rate([1.0, 10.0, 20.0]) == 15.0


class TestPersistResult:
    def test_atomic_and_corrupt_preserving(self, common, tmp_path,
                                           monkeypatch):
        bdir = tmp_path / "benchmarks"
        bdir.mkdir()
        monkeypatch.setattr(common, "__file__", str(bdir / "common.py"))
        out = tmp_path / "chiprun_out"
        out.mkdir()
        path = out / "bench_results.json"
        path.write_text('{"results": {"old":')  # torn
        common.persist_result("fresh", {"value": 7})
        doc = json.loads(path.read_text())
        assert doc["results"]["fresh"]["result"]["value"] == 7
        assert path.with_name("bench_results.json.corrupt").exists()
        # merge path keeps existing rows
        common.persist_result("second", {"value": 8})
        doc = json.loads(path.read_text())
        assert set(doc["results"]) == {"fresh", "second"}

    def test_creates_the_output_directory(self, common, tmp_path,
                                          monkeypatch):
        bdir = tmp_path / "benchmarks"
        bdir.mkdir()
        monkeypatch.setattr(common, "__file__", str(bdir / "common.py"))
        common.persist_result("row", {"value": 1})
        assert (tmp_path / "chiprun_out" / "bench_results.json").exists()


class TestDeviceSync:
    """The timing barrier: `jax.block_until_ready` on every leaf (the chip
    smoke showed it waiting on this machine), element 0 of the first leaf
    back for the caller's finiteness check."""

    def test_returns_first_element(self, world, common):
        import jax.numpy as jnp

        assert common.device_sync(jnp.float32(3.5)) == 3.5
        assert common.device_sync(jnp.arange(5.0) + 2) == 2.0
        tree = {"a": jnp.full((4, 4), 6.0), "b": jnp.float32(4.0)}
        assert common.device_sync(tree) == 6.0

    def test_fetches_one_element_not_the_leaf(self, world, common,
                                              monkeypatch):
        """The barrier sits inside timed windows: it may bring ONE element
        to the host, from a shard this process holds."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), ("x",))
        big = jax.device_put(
            jnp.full((len(jax.devices()) * 64, 128), 2.0),
            NamedSharding(mesh, P("x")),
        )
        fetched = []
        real = np.asarray

        def spy(a, *args, **kw):
            fetched.append(int(getattr(a, "size", 1)))
            return real(a, *args, **kw)

        monkeypatch.setattr(np, "asarray", spy)
        assert common.device_sync(big) == 2.0
        assert max(fetched) < 64 * 128, fetched

    def test_disttensor_unwraps(self, world, common):
        import numpy as np

        import pytorch_distributed_example_tpu as tdx
        from pytorch_distributed_example_tpu.tensor import DistTensor

        g = tdx.distributed._get_default_group()
        dt = DistTensor.from_process_local(np.full(4, 3.0, np.float32), g)
        assert common.device_sync(dt) == 3.0

    def test_errors_propagate_not_swallowed(self, world, common,
                                            monkeypatch):
        import jax
        import jax.numpy as jnp

        # how async device errors (an OOM) reach the host: a regression
        # wrapping the barrier in try/except would turn them into a
        # silently "passing" bench
        def boom(_):
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")

        monkeypatch.setattr(jax, "block_until_ready", boom)
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            common.device_sync(jnp.float32(1))

    def test_on_tpu_reads_the_platform_only(self, world, common):
        assert common.on_tpu() is False


class TestRunAllFailsWhenAnyBenchFails:
    def _run(self, tmp_path, *args):
        return subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmarks", "run_all.py"),
             "--out", str(tmp_path / "out.json"), *args],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )

    def test_chip_only_job_fails_the_sweep_off_tpu(self, tmp_path):
        r = self._run(tmp_path, "--only", "headline", "--quick")
        assert r.returncode != 0, r.stdout + r.stderr
        assert "FAILED: ['headline']" in r.stdout
        doc = json.loads((tmp_path / "out.json").read_text())
        assert doc["results"]["headline"]["rc"] != 0
        assert "no TPU" in doc["results"]["headline"]["stderr_tail"]

    def test_cpu_sweep_leaves_chip_only_jobs_out(self, tmp_path):
        r = self._run(tmp_path, "--cpu", "--only", "headline")
        assert r.returncode == 2  # argparse: not a job of the --cpu sweep
        assert "unknown job" in r.stderr
