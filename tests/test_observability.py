"""Aux-subsystem tests: status, flight recorder, watchdog, debug wrapper,
DDP logging data (SURVEY.md §5.1/§5.2/§5.3/§5.5)."""

import json
import time

import numpy as np
import pytest

import pytorch_distributed_example_tpu as tdx
from pytorch_distributed_example_tpu.types import ReduceOp


class TestProcessGroupStatus:
    def test_status_tracks_collectives(self, world):
        g = tdx.new_group(backend="xla")
        t = tdx.DistTensor.from_rank_fn(
            lambda r: np.ones((3,), np.float32), g
        )
        w = tdx.all_reduce(t, group=g, async_op=True)
        assert g.status.last_enqueued_op == "all_reduce"
        assert g.status.last_enqueued_numel == 3 * world.size()  # rank-stacked
        seq = g.status.last_enqueued_seq
        w.wait()
        assert g.status.last_completed_seq == seq
        assert g.status.last_completed_op == "all_reduce"


class TestFlightRecorder:
    def test_records_and_dumps(self, world, tmp_path):
        from pytorch_distributed_example_tpu.utils.flight_recorder import (
            DebugInfoWriter,
            FlightRecorder,
            global_recorder,
        )

        rec = global_recorder()
        n0 = len(rec.entries())
        t = tdx.DistTensor.from_rank_fn(lambda r: np.ones((4,), np.float32))
        tdx.all_reduce(t, async_op=True).wait()
        entries = rec.entries()
        assert len(entries) > n0
        last = entries[-1]
        assert last.op == "all_reduce"
        assert last.shape[-1] == 4
        assert last.state == "completed"

        writer = DebugInfoWriter(str(tmp_path))
        path = writer.write(rec, reason="test")
        with open(path) as f:
            payload = json.load(f)
        assert payload["version"] == "tdx-1.0"
        assert payload["reason"] == "test"
        assert payload["entries"]

    def test_ring_bounded(self):
        from pytorch_distributed_example_tpu.utils.flight_recorder import (
            FlightRecorder,
        )

        rec = FlightRecorder(capacity=10)
        for i in range(50):
            rec.record(i, "op", "g", (1,), "f32", 1)
        assert len(rec.entries()) == 10
        assert rec.entries()[0].seq == 40


class TestWatchdog:
    def test_timeout_trips_and_dumps(self, tmp_path):
        from pytorch_distributed_example_tpu.types import Work
        from pytorch_distributed_example_tpu.utils.flight_recorder import (
            DebugInfoWriter,
            FlightRecorder,
        )
        from pytorch_distributed_example_tpu.utils.watchdog import Watchdog

        class NeverDone(Work):
            def is_completed(self):
                return False

        trips = []
        wd = Watchdog(
            timeout_s=0.2,
            poll_interval_s=0.05,
            on_timeout=lambda desc, w, p: trips.append((desc, p)),
            recorder=FlightRecorder(),
            writer=DebugInfoWriter(str(tmp_path)),
        ).start()
        hung = NeverDone()
        wd.register(hung, "test:hung:1")
        deadline = time.monotonic() + 5
        while not trips and time.monotonic() < deadline:
            time.sleep(0.05)
        wd.stop()
        assert trips and trips[0][0] == "test:hung:1"
        assert trips[0][1]  # dump path written

    def test_subgroup_inherits_watchdog_coverage(self, world):
        """A collective hung on a `new_group` subgroup must be visible to
        hang detection, as torch's NCCL watchdog covers every PG, not
        just WORLD (round-4 advisor). Arming the default group makes
        groups created afterwards arm themselves."""
        import pytorch_distributed_example_tpu as tdx
        from pytorch_distributed_example_tpu import distributed as dist

        assert world.watchdog is None  # precondition: not armed by env
        try:
            dist._arm_abort_watchdog(world)
            sub = tdx.new_group(list(range(world.size()))[:2])
            assert sub.watchdog is not None, (
                "subgroup created under an armed default watchdog must "
                "be scanned too"
            )
            tdx.destroy_process_group(sub)
            assert sub.watchdog is None  # destroy stops the scanner
        finally:
            if world.watchdog is not None:
                world.watchdog.stop()
                world.watchdog = None

    def test_completed_work_not_flagged(self):
        from pytorch_distributed_example_tpu.types import CompletedWork
        from pytorch_distributed_example_tpu.utils.watchdog import Watchdog

        trips = []
        wd = Watchdog(
            timeout_s=0.1,
            poll_interval_s=0.05,
            on_timeout=lambda *a: trips.append(a),
            dump_on_timeout=False,
        ).start()
        wd.register(CompletedWork(), "done")
        time.sleep(0.4)
        wd.stop()
        assert not trips

    def test_heartbeat_monitor_detects_stuck(self):
        from pytorch_distributed_example_tpu.utils.watchdog import (
            HeartbeatMonitor,
            Watchdog,
        )

        wd = Watchdog(timeout_s=10)  # never started -> heartbeat frozen
        wd.last_heartbeat = time.monotonic() - 100
        stuck = []
        hb = HeartbeatMonitor(
            wd, heartbeat_timeout_s=0.1, kill_process=False,
            on_stuck=lambda age: stuck.append(age),
        ).start()
        deadline = time.monotonic() + 3
        while not stuck and time.monotonic() < deadline:
            time.sleep(0.05)
        hb.stop()
        assert stuck and stuck[0] > 0.1


class TestDebugWrapper:
    def test_wrapper_passthrough_and_mismatch(self, world):
        from pytorch_distributed_example_tpu.backends.wrapper import (
            CollectiveMismatchError,
            ProcessGroupWrapper,
        )
        from pytorch_distributed_example_tpu.store import HashStore

        g = tdx.distributed._get_default_group()
        store = HashStore(5.0)
        wrapped = ProcessGroupWrapper(
            g.backend_impl, store, my_rank=0, world_size=world.size(),
            driver_mode=True,
        )
        t = tdx.DistTensor.from_rank_fn(lambda r: np.full((2,), r, np.float32))
        out, work = wrapped.allreduce(t.array, ReduceOp.SUM)
        work.wait()
        np.testing.assert_allclose(
            np.asarray(out)[0], sum(range(world.size()))
        )
        # fingerprint was published
        assert store.num_keys() >= 1

        # multiproc-mode mismatch: rank 0 publishes a different op under the
        # same seq than we then verify for
        store2 = HashStore(0.5)
        w2 = ProcessGroupWrapper(
            g.backend_impl, store2, my_rank=1, world_size=2, driver_mode=False
        )
        store2.set("pgw/1/0", "broadcast:0|(2,)|float32")
        with pytest.raises(CollectiveMismatchError):
            w2.allreduce(t.array, ReduceOp.SUM)


class TestDDPLogger:
    def test_logging_data(self, world):
        import jax
        import jax.numpy as jnp
        import optax

        from pytorch_distributed_example_tpu.models import ConvNet
        from pytorch_distributed_example_tpu.utils.logger import DDPLogger

        model = ConvNet()
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))
        ddp = tdx.DistributedDataParallel(model, params)
        log = DDPLogger(ddp)
        log.step_begin()
        time.sleep(0.01)
        log.step_end()
        data = log.get_ddp_logging_data()
        assert data["world_size"] == world.size()
        assert data["backend_name"] == "xla"
        assert data["bucket_cap_bytes"] == 25 * 1024 * 1024
        assert data["num_steps"] == 1
        assert data["avg_step_time_s"] > 0


class TestProfilingTier:
    """Round-2 §5.1 parity: component times in DDPLoggingData + opt-in
    jax.profiler trace (torch reducer.hpp:468-472, logger.hpp:85-90)."""

    def _setup(self, world):
        import jax
        import jax.numpy as jnp
        import optax

        from pytorch_distributed_example_tpu.models import ConvNet

        model = ConvNet()
        params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))
        ddp = tdx.DistributedDataParallel(model, params)
        opt = optax.sgd(0.05)

        def loss_fn(logits, y):
            import optax as _o

            return _o.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        W = world.size()
        x = np.random.default_rng(0).standard_normal((2 * W, 28, 28, 1)).astype(np.float32)
        y = np.random.default_rng(1).integers(0, 10, 2 * W).astype(np.int32)
        return ddp, opt, loss_fn, x, y

    def test_step_timing_recorded_by_train_step(self, world):
        import optax

        ddp, opt, loss_fn, x, y = self._setup(world)
        step = ddp.make_train_step(opt, loss_fn)
        ddp.logger.enable_step_timing()
        p, o = ddp.params, opt.init(ddp.params)
        for _ in range(3):
            p, o, _ = step(p, o, x, y)
        data = ddp.get_ddp_logging_data()
        assert data["num_steps"] == 3
        assert data["avg_step_time_s"] > 0

    def test_profile_breakdown_fills_component_times(self, world):
        """What `profile_breakdown` guarantees by construction, not how
        two CPU timings of three iterations happen to compare: a component
        is a difference of wall times clamped at 0, so each is >= 0 and,
        when none was clamped, the four telescope to the full step. On a
        TPU the decomposition to read is `bench_matrix.reduce.scopes.table`
        of a traced step (device time by program component)."""
        ddp, opt, loss_fn, x, y = self._setup(world)
        out = ddp.profile_breakdown(opt, loss_fn, x, y, iters=3)
        data = ddp.get_ddp_logging_data()
        parts = ("forward_s", "backward_s", "optimizer_s", "comm_exposed_s")
        assert out["full_step_s"] > 0 and out["forward_s"] > 0
        assert all(out[k] >= 0 for k in parts)
        if all(out[k] > 0 for k in parts[1:]):  # nothing clamped
            assert sum(out[k] for k in parts) == pytest.approx(out["full_step_s"])
        else:
            assert sum(out[k] for k in parts) >= out["full_step_s"]
        assert data["avg_forward_compute_time_s"] == out["forward_s"]
        assert data["avg_backward_compute_time_s"] == out["backward_s"]
        assert data["avg_optimizer_time_s"] == out["optimizer_s"]
        assert data["avg_backward_comm_time_s"] == out["comm_exposed_s"]

    def test_profiler_trace_context_writes_trace(self, world, tmp_path):
        ddp, opt, loss_fn, x, y = self._setup(world)
        step = ddp.make_train_step(opt, loss_fn)
        logdir = str(tmp_path / "trace")
        with ddp.logger.profiler_trace(logdir):
            p, o = ddp.params, opt.init(ddp.params)
            p, o, _ = step(p, o, x, y)
        import os as _os

        found = []
        for root, _, files in _os.walk(logdir):
            found.extend(files)
        assert found, "profiler trace produced no files"


class TestDebugHTTPFrontend:
    """torch debug/_frontend.py parity (§5.5): live state over HTTP."""

    def test_routes_serve_runtime_state(self, world):
        import json
        import urllib.request

        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import ConvNet
        from pytorch_distributed_example_tpu.utils.debug_http import DebugServer

        srv = DebugServer()
        try:
            def get(path):
                with urllib.request.urlopen(srv.url + path, timeout=10) as r:
                    return json.loads(r.read().decode())

            idx = get("/")
            assert "/status" in idx["routes"]

            w = get("/world")
            assert w["initialized"] and w["mode"] == "driver"
            assert "default_pg" in w["groups"]

            # drive one collective so status/flight recorder have content
            t = tdx.DistTensor.from_rank_fn(
                lambda r: np.array([float(r)], np.float32)
            )
            tdx.all_reduce(t)
            t.block_until_ready()

            st = get("/status")
            assert st["default_pg"]["last_enqueued_op"] == "all_reduce"

            fr = get("/flight_recorder")
            assert any(e.get("op") == "all_reduce" for e in fr["entries"])

            model = ConvNet()
            params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))
            ddp = tdx.DistributedDataParallel(model, params)
            srv.register_ddp_logger("convnet", ddp.logger)
            dl = get("/ddp_logging")
            assert dl["convnet"]["world_size"] == world.size()

            # unknown route -> 404
            import urllib.error

            try:
                get("/nope")
                assert False, "expected 404"
            except urllib.error.HTTPError as e:
                assert e.code == 404
        finally:
            srv.shutdown()
