"""Deviceless TPU-target AOT compilation — the path the round-4 memory
and ceiling evidence rides (benchmarks/llama_scaled.py --target tpu,
benchmarks/tpu_aot_check.py).

jax.experimental.topologies gives a compile-only TPU client: the real
PJRT TPU compiler runs on the host with no chip attached, so XLA's
memory_analysis/cost_analysis are TPU-backend facts. These tests pin
that the plumbing works (topology resolves, single- and multi-device
compiles succeed, the analyses expose the fields the benches read) so
a JAX upgrade can't silently rot the evidence path.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # TPU-target compiles take tens of seconds


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # pragma: no cover - environment-dependent
        pytest.skip(f"deviceless TPU topology unavailable: {e}")


def test_topology_exposes_devices(topo):
    devs = list(topo.devices)
    assert len(devs) == 4
    assert "tpu" in devs[0].device_kind.lower() or "TPU" in devs[0].device_kind


def test_single_device_compile_cost_and_memory(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    dev = topo.devices[0]
    x = jax.ShapeDtypeStruct(
        (256, 256), jnp.bfloat16, sharding=SingleDeviceSharding(dev)
    )
    compiled = jax.jit(lambda a: a @ a).lower(x).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    # one 256^3 matmul = 2*256^3 flops; cost model must be in range
    assert 1e7 < float(ca.get("flops", 0)) < 1e9
    ma = compiled.memory_analysis()
    if isinstance(ma, (list, tuple)):
        ma = ma[0]
    assert int(ma.argument_size_in_bytes) == 256 * 256 * 2


def test_sharded_mesh_compile_memory_analysis(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("a", "b"))
    x = jax.ShapeDtypeStruct(
        (512, 512), jnp.bfloat16, sharding=NamedSharding(mesh, P("a", None))
    )
    compiled = jax.jit(lambda v: (v @ v.T).sum()).lower(x).compile()
    ma = compiled.memory_analysis()
    if isinstance(ma, (list, tuple)):
        ma = ma[0]
    # per-DEVICE argument bytes: the (512,512) bf16 input sharded 2-way
    assert int(ma.argument_size_in_bytes) == 512 * 512 * 2 // 2


def test_fully_shard_lm_step_with_flash_compiles_under_mesh(topo, monkeypatch):
    """The only check in a chipless sandbox that sees a Mosaic kernel under
    a multi-chip GSPMD trainer: the (fsdp, tp) = (2, 2) `fully_shard` LM
    step with flash ON compiles for v5e:2x2, and every custom call runs on
    a LOCAL (batch-shard x head-shard) slice. On the CPU mesh the kernel is
    interpreted — plain HLO that GSPMD partitions — so tier-1 cannot tell;
    without `ops.partitioned_over` this lowering raises "Mosaic kernels
    cannot be automatically partitioned"."""
    import re

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
        transformer_sharding_rules,
    )
    from pytorch_distributed_example_tpu.parallel import sharding as shd
    from pytorch_distributed_example_tpu.parallel.fsdp import (
        make_fsdp_train_step,
    )

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")  # TPU target, CPU process
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("fsdp", "tp"))
    B, L, H, Dh = 4, 1024, 8, 128
    cfg = TransformerConfig(
        vocab_size=2048, d_model=H * Dh, n_layers=1, n_heads=H, d_ff=2048,
        max_seq_len=L, dtype=jnp.bfloat16, use_flash=True, remat=True,
    )
    model = TransformerLM(cfg)
    abs_params = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0),
    )
    specs = shd.make_param_specs(
        abs_params, transformer_sharding_rules("tp", "fsdp"), mesh
    )
    opt = optax.adamw(1e-3)

    def loss_fn(logits, y):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], y[:, 1:]
        ).mean()

    step = make_fsdp_train_step(
        model.apply, loss_fn, opt, mesh, specs, data_axes=("fsdp",)
    )
    sd = lambda l, s: jax.ShapeDtypeStruct(
        l.shape, l.dtype, sharding=NamedSharding(mesh, s)
    )
    p_abs = jax.tree_util.tree_map(sd, abs_params, specs)
    o_abs = jax.eval_shape(step.init_opt_state, p_abs)
    x = sd(jax.ShapeDtypeStruct((B, L), jnp.int32), P("fsdp"))
    hlo = step.lower(p_abs, o_abs, x, x).compile().as_text()

    calls = [
        line for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    assert calls, "no Mosaic custom call in the compiled step"
    # kernels see (B*H, L, Dh) per device: batch/2 rows x heads/2 heads
    local_bh = (B // 2) * (H // 2)
    for line in calls:
        operands = line.split("custom-call(", 1)[1]
        shapes = set(re.findall(r"bf16\[(\d+),(\d+),(\d+)\]", operands))
        assert shapes == {(str(local_bh), str(L), str(Dh))}, line
