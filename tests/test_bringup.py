"""Unit tests for what PR 21 (chip bring-up) put in the package: the one
compile-cache rule, the one trace probe, flash under a mesh, the native
library's visible build path, and one chip per launched worker."""

import os

import numpy as np
import pytest

from pytorch_distributed_example_tpu import _compat, traceguard


class TestCompileCacheHelper:
    KEY = "jax_compilation_cache_dir"

    @pytest.fixture()
    def updates(self, monkeypatch):
        """What the helper sets through `jax.config.update`, recorded
        instead of applied (the suite's own cache setting stays put)."""
        import jax

        calls = {}
        monkeypatch.setattr(
            jax.config, "update", lambda key, value: calls.update({key: value})
        )
        return calls

    def test_env_set_means_nothing_is_set_in_code(
        self, monkeypatch, updates, tmp_path
    ):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        # the machine's pin wins over a directory a caller names, too
        assert _compat.enable_compile_cache("/elsewhere") == str(tmp_path)
        assert self.KEY not in updates
        # wherever the cache lives, a program that differs only in its
        # scope names must not load the executable that has the old ones
        assert updates["jax_compilation_cache_include_metadata_in_key"] is True

    def test_unset_uses_the_fixed_in_checkout_path(self, monkeypatch, updates):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        got = _compat.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(root, ".jax_compile_cache")
        assert updates[self.KEY] == got
        # derived from the package path alone: the same on every call
        assert _compat.enable_compile_cache() == got

    def test_named_directory_is_used_when_nothing_is_pinned(
        self, monkeypatch, updates, tmp_path
    ):
        from pytorch_distributed_example_tpu.serve import prewarm

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert prewarm.enable_compile_cache(str(tmp_path)) == str(tmp_path)
        assert updates[self.KEY] == str(tmp_path)
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0

    def test_the_suite_itself_runs_on_the_helpers_choice(self):
        import jax

        want = os.environ.get(
            "JAX_COMPILATION_CACHE_DIR", _compat.COMPILE_CACHE_DIR
        )
        assert getattr(jax.config, self.KEY) == want

    def test_cache_dir_is_git_ignored(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, ".gitignore")) as f:
            ignored = f.read().split()
        assert os.path.basename(_compat.COMPILE_CACHE_DIR) + "/" in ignored


class TestTraceProbe:
    def test_false_at_top_level(self):
        assert traceguard.under_tracing() is False

    def _raising_body(self, seen):
        def body(x):
            seen.append(traceguard.under_tracing())
            return x + 1

        return body

    def test_true_under_jit(self):
        import jax
        import jax.numpy as jnp

        seen = []
        jax.jit(self._raising_body(seen))(jnp.zeros(()))
        assert seen == [True]

    def test_true_under_shard_map(self, world):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        seen = []
        mesh = world.mesh.jax_mesh
        axis = mesh.axis_names[0]
        _compat.shard_map_fn(
            self._raising_body(seen), mesh, P(axis), P(axis)
        )(jnp.zeros((mesh.size,)))
        assert seen and all(seen)

    def test_true_under_scan(self):
        import jax
        import jax.numpy as jnp

        seen = []

        def step(carry, x):
            seen.append(traceguard.under_tracing())
            return carry + x, x

        jax.lax.scan(step, jnp.zeros(()), jnp.ones((3,)))
        assert seen and all(seen)

    def test_guarded_op_raises_under_shard_map_and_scan(
        self, world, monkeypatch
    ):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        monkeypatch.setenv("TDX_TRACE_GUARD", "1")

        def body(x):
            traceguard.check("probe.op")
            return x

        mesh = world.mesh.jax_mesh
        axis = mesh.axis_names[0]
        with pytest.raises(traceguard.TraceGuardError, match="probe.op"):
            _compat.shard_map_fn(body, mesh, P(axis), P(axis))(
                jnp.zeros((mesh.size,))
            )
        with pytest.raises(traceguard.TraceGuardError, match="probe.op"):
            jax.lax.scan(lambda c, x: (body(c), x), 0.0, jnp.ones((2,)))

    def test_missing_api_raises_instead_of_reading_as_not_tracing(
        self, monkeypatch
    ):
        import jax

        monkeypatch.delattr(jax.core, "trace_ctx")
        with pytest.raises(AttributeError):
            traceguard.under_tracing()


class TestFlashUnderMesh:
    def _qkv(self, B=4, L=64, H=4, D=16):
        import jax
        import jax.numpy as jnp

        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        return tuple(jax.random.normal(k, (B, L, H, D), jnp.float32)
                     for k in keys)

    def _mesh(self, shape=(2, 2)):
        import jax
        from jax.sharding import Mesh

        n = shape[0] * shape[1]
        return Mesh(np.array(jax.devices()[:n]).reshape(shape),
                    ("fsdp", "tp"))

    def test_partitioned_matches_unpartitioned_fwd_and_grad(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from pytorch_distributed_example_tpu.ops import (
            flash_attention,
            partitioned_over,
        )

        q, k, v = self._qkv()
        mesh = self._mesh()

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        want, want_g = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        sh = NamedSharding(mesh, P("fsdp", None, "tp", None))

        @jax.jit
        def sharded(q, k, v):
            with partitioned_over(mesh, ("fsdp",), ("tp",)):
                return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

        got, got_g = sharded(*(jax.device_put(x, sh) for x in (q, k, v)))
        np.testing.assert_allclose(got, want, rtol=1e-5)
        for a, b in zip(got_g, want_g):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
        # the kernel ran per device: outputs keep the (batch, head) layout
        assert got_g[0].sharding.is_equivalent_to(sh, 4)

    def test_context_closes_and_nests(self):
        from pytorch_distributed_example_tpu.ops import partitioned_over

        _partition = _flash_module()._partition
        mesh = self._mesh()
        assert _partition.spec is None
        with partitioned_over(mesh, ("fsdp",), ("tp",)):
            with partitioned_over(mesh, ("fsdp", "tp")):
                assert _partition.spec[1:] == (("fsdp", "tp"), ())
            assert _partition.spec[1:] == (("fsdp",), ("tp",))
        assert _partition.spec is None

    def test_indivisible_heads_raise_by_name(self):
        from pytorch_distributed_example_tpu.ops import (
            flash_attention,
            partitioned_over,
        )

        q, k, v = self._qkv(H=3)
        with partitioned_over(self._mesh(), ("fsdp",), ("tp",)):
            with pytest.raises(ValueError, match=r"heads 3 over \('tp',\)"):
                flash_attention(q, k, v)

    def test_unknown_axis_raises(self):
        from pytorch_distributed_example_tpu.ops import partitioned_over

        with pytest.raises(ValueError, match="no axis 'mp'"):
            with partitioned_over(self._mesh(), ("fsdp",), ("mp",)):
                pass

    def test_gqa_model_trains_under_tp_that_does_not_divide_kv_heads(self):
        """n_kv_heads=1 at tp=2: K/V are repeated to full heads before the
        call, so the kernel shards over H, not KV — it must work."""
        import jax
        import jax.numpy as jnp
        import optax

        from pytorch_distributed_example_tpu.mesh import init_device_mesh
        from pytorch_distributed_example_tpu.models import (
            TransformerConfig,
            TransformerLM,
            transformer_sharding_rules,
        )
        from pytorch_distributed_example_tpu.parallel import fully_shard

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=1, n_heads=4, n_kv_heads=1,
            max_seq_len=32, use_flash=True,
        )
        model = TransformerLM(cfg)
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, 64, (4, 32)), jnp.int32
        )
        params = model.init(jax.random.PRNGKey(0), toks[:1])
        mesh = init_device_mesh(
            ("fsdp", "tp"), (2, 2), devices=jax.devices()[:4]
        )
        mod = fully_shard(
            model, params, mesh, axis="fsdp",
            rules=transformer_sharding_rules("tp", "fsdp"),
            data_axes=("fsdp",),
        )
        dense = TransformerLM(
            TransformerConfig(**{**cfg.__dict__, "use_flash": False})
        )
        # eager forward of the sharded module (FSDPModule.__call__) agrees
        # with dense attention on one device
        np.testing.assert_allclose(
            mod(toks), dense.apply(params, toks), rtol=2e-4, atol=2e-4
        )

        def loss_fn(logits, y):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], y[:, 1:]
            ).mean()

        opt = optax.adam(1e-2)
        step = mod.make_train_step(opt, loss_fn)
        p, o = mod.params, step.init_opt_state(mod.params)
        losses = []
        for _ in range(3):
            p, o, loss = step(p, o, toks, toks)
            losses.append(float(loss))
        assert np.isfinite(losses).all() and losses[-1] < losses[0]
        # one program: the state init_opt_state builds is in the layout
        # the step returns, so the second call does not compile again
        assert step._cache_size() == 1


class TestHeadAxesComeFromTheSpecs:
    """The trainer tells the kernel which mesh axes shard heads from the
    q_proj specs, never by guessing from the mesh's axis names."""

    def _lm(self, n_heads=2):
        import jax
        import jax.numpy as jnp

        from pytorch_distributed_example_tpu.models import (
            TransformerConfig,
            TransformerLM,
        )

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_layers=1, n_heads=n_heads,
            max_seq_len=32, use_flash=True,
        )
        model = TransformerLM(cfg)
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, 64, (4, 32)), jnp.int32
        )
        return model, model.init(jax.random.PRNGKey(0), toks[:1]), toks

    def test_read_off_the_rules(self):
        from pytorch_distributed_example_tpu.models import (
            transformer_sharding_rules,
        )
        from pytorch_distributed_example_tpu.parallel import sharding as shd
        from pytorch_distributed_example_tpu.parallel.fsdp import (
            head_axes_from_specs,
        )

        _, params, _ = self._lm()
        tp = shd.make_param_specs(
            params, transformer_sharding_rules("model", "fsdp")
        )
        assert head_axes_from_specs(tp) == ("model",)
        # pure FSDP shards dim 0 only: heads are whole on every device
        dim0 = shd.make_param_specs(params, shd.fsdp_rules("fsdp"))
        assert head_axes_from_specs(dim0) == ()
        # a model with no q_proj has no heads to place
        assert head_axes_from_specs({"w": dim0["params"]["lm_head"]}) == ()

    def test_layers_that_disagree_raise_by_path(self):
        from jax.sharding import PartitionSpec as P

        from pytorch_distributed_example_tpu.parallel.fsdp import (
            head_axes_from_specs,
        )

        specs = {
            "layers_0": {"q_proj": {"kernel": P("fsdp", "tp")}},
            "layers_1": {"q_proj": {"kernel": P("fsdp", "ep")}},
        }
        with pytest.raises(ValueError, match="layers_1/q_proj/kernel"):
            head_axes_from_specs(specs)

    def test_a_third_mesh_axis_is_not_taken_for_a_head_axis(self):
        """("fsdp","tp","ep") = (2,2,2) with 2 heads: heads go over tp
        alone. Guessing 'every non-data axis' would put 2 heads over
        tp x ep = 4 and raise where the step runs."""
        import jax
        import optax

        from pytorch_distributed_example_tpu.mesh import init_device_mesh
        from pytorch_distributed_example_tpu.models import (
            transformer_sharding_rules,
        )
        from pytorch_distributed_example_tpu.parallel import fully_shard

        model, params, toks = self._lm(n_heads=2)
        mesh = init_device_mesh(("fsdp", "tp", "ep"), (2, 2, 2))
        mod = fully_shard(
            model, params, mesh, axis="fsdp",
            rules=transformer_sharding_rules("tp", "fsdp"),
            data_axes=("fsdp",),
        )
        assert mod.head_axes == ("tp",)

        def loss_fn(logits, y):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], y[:, 1:]
            ).mean()

        step = mod.make_train_step(optax.adam(1e-2), loss_fn)
        p, o = mod.params, step.init_opt_state(mod.params)
        first = last = None
        for _ in range(3):
            p, o, loss = step(p, o, toks, toks)
            first, last = (float(loss) if first is None else first), float(loss)
        assert np.isfinite(last) and last < first

    def test_an_axis_cannot_shard_both_batch_and_heads(self):
        import jax

        from pytorch_distributed_example_tpu.mesh import init_device_mesh
        from pytorch_distributed_example_tpu.parallel.fsdp import (
            _kernel_partition,
        )

        mesh = init_device_mesh(
            ("fsdp", "tp"), (2, 2), devices=jax.devices()[:4]
        ).jax_mesh
        with pytest.raises(ValueError, match=r"\['tp'\] are both data axes"):
            _kernel_partition(mesh, ("fsdp", "tp"), ("tp",))


def _flash_module():
    """The MODULE: `ops.flash_attention` the attribute is the function."""
    import importlib

    return importlib.import_module(
        "pytorch_distributed_example_tpu.ops.flash_attention"
    )


class TestNoSilentKernelFallback:
    def test_interpret_default_follows_the_backend(self, monkeypatch):
        import jax

        fa = _flash_module()

        monkeypatch.delenv("TDX_FLASH_INTERPRET", raising=False)
        assert fa._interpret_default() is True  # cpu backend
        monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")  # deviceless AOT
        assert fa._interpret_default() is False
        # on a TPU backend nothing reaches the interpreter
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv("TDX_FLASH_INTERPRET", "1")
        assert fa._interpret_default() is False

    def test_unreadable_tuned_table_raises(self, monkeypatch, tmp_path):
        fa = _flash_module()
        fa._tuned_table.cache_clear()
        monkeypatch.setattr(fa, "__file__", str(tmp_path / "flash.py"))
        try:
            with pytest.raises(FileNotFoundError):
                fa._tuned_table()
            (tmp_path / "flash_tuned.json").write_text("{torn")
            with pytest.raises(ValueError):
                fa._tuned_table()
        finally:
            fa._tuned_table.cache_clear()

    def test_dense_routing_is_announced(self):
        from pytorch_distributed_example_tpu.models.transformer import (
            _flash_ok,
        )

        assert _flash_ok(256, 64)
        with pytest.warns(RuntimeWarning, match="DENSE attention"):
            assert not _flash_ok(1000, 64)
        with pytest.warns(RuntimeWarning, match="head_dim=512"):
            assert not _flash_ok(256, 512)


class TestNativeBuildIsVisible:
    def test_status_names_the_path_taken(self):
        from pytorch_distributed_example_tpu import _native

        status = _native.status()
        if _native.available():
            assert status.startswith("native: ") and "libtdx.so" in status
        else:
            assert status.startswith("python: ")

    def test_disabled_by_env_says_so(self, monkeypatch):
        from pytorch_distributed_example_tpu import _native

        monkeypatch.setenv("TDX_NATIVE", "0")
        assert _native.load() is None
        assert _native.status() == "python: TDX_NATIVE=0"

    def test_make_failure_is_reported_not_swallowed(self, monkeypatch):
        from pytorch_distributed_example_tpu import _native

        monkeypatch.setattr(_native, "_CSRC", "/nonexistent/csrc")
        reason = _native._make()
        assert reason is not None and "make" in reason


class TestOneChipPerWorker:
    def _envs(self, monkeypatch, chips, nproc, platforms="tpu,cpu"):
        from pytorch_distributed_example_tpu.elastic import agent

        monkeypatch.setattr(agent, "local_tpu_chips", lambda: chips)
        ports = iter(range(9001, 9100))
        return agent.tpu_chip_envs(
            nproc, {"JAX_PLATFORMS": platforms}, lambda: next(ports)
        )

    def test_four_workers_on_four_chips_get_one_chip_each(self, monkeypatch):
        envs = self._envs(monkeypatch, 4, 4)
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == ["0", "1", "2", "3"]
        assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {"2,2,1"}
        assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {"1,1,1"}
        assert [e["TPU_PROCESS_PORT"] for e in envs] == [
            "9001", "9002", "9003", "9004"
        ]
        assert {e["TPU_PROCESS_ADDRESSES"] for e in envs} == {
            "localhost:9001,localhost:9002,localhost:9003,localhost:9004"
        }
        # the image's one-process-owns-all bounds must not leak through
        assert envs[2]["TPU_HOST_BOUNDS"] == "2,2,1"
        assert envs[2]["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"

    def test_nothing_is_set_off_tpu_or_in_driver_mode(self, monkeypatch):
        assert self._envs(monkeypatch, 0, 2) == [{}, {}]  # no chips
        assert self._envs(monkeypatch, 4, 1) == [{}]  # one owner of all
        assert self._envs(monkeypatch, 4, 2, platforms="cpu") == [{}, {}]

    def test_wrong_worker_count_fails_at_once_by_name(self, monkeypatch):
        with pytest.raises(RuntimeError, match="3 workers on a host with 4"):
            self._envs(monkeypatch, 4, 3)
        with pytest.raises(RuntimeError, match="one worker per chip"):
            self._envs(monkeypatch, 1, 2)
