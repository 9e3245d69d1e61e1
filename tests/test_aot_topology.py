"""Deviceless TPU-target AOT compilation.

jax.experimental.topologies gives a compile-only TPU client: the real
PJRT TPU compiler runs on the host with no chip attached, so XLA's
memory_analysis/cost_analysis are TPU-backend facts. These tests pin
that the plumbing works (topology resolves, single- and multi-device
compiles succeed, the analyses expose the fields the benches read) so
a JAX upgrade can't silently rot the evidence path.
"""

import re

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


@pytest.mark.parametrize("rung,kernel_calls", [(0, 3), (3, 2)])
def test_fully_shard_lm_step_with_flash_compiles_under_mesh(
    topo, monkeypatch, rung, kernel_calls
):
    """The only check in a chipless sandbox that sees a Mosaic kernel under
    a multi-chip GSPMD trainer: the (fsdp, tp) = (2, 2) `fully_shard` LM
    step with flash ON compiles for v5e:2x2, and every custom call runs on
    a LOCAL (batch-shard x head-shard) slice. On the CPU mesh the kernel is
    interpreted — plain HLO that GSPMD partitions — so tier-1 cannot tell;
    without `ops.partitioned_over` this lowering raises "Mosaic kernels
    cannot be automatically partitioned".

    A described device cannot be asked for its memory limit
    (`utils.remat.device_limit_bytes` is None, nothing steered here), so
    the step is the plain program at rung 0 of the remat ladder: the
    layer's forward kernel runs twice (forward, recomputed, the one backward).
    With a limit handed in, the fit runs as on a chip (lower, compile for
    v5e, the compiler's count), takes the top rung, and the names inside
    the kernel's forward rule hold through the `shard_map`: the compiled
    step runs it once."""
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

    from pytorch_distributed_example_tpu.utils import remat

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")  # TPU target, CPU process
    if rung:
        monkeypatch.setattr(remat, "device_limit_bytes", lambda devices: 10**15)
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

    calls = _custom_calls(hlo)
    assert len(calls) == kernel_calls
    # under the shard_map too the calls are named after their kernels
    names = _call_names(calls)
    assert names == ["flash_bwd"] + ["flash_fwd"] * (kernel_calls - 1), names
    plan = step.remat_plan
    if rung == 0:
        assert plan is None
    else:
        # the first rung compiled fitted: 16 GB of HBM hold it by the count
        assert plan.rung == rung and 0 < plan.held[0][1] < 16e9
    # kernels see (B*H, L, Dh) per device: batch/2 rows x heads/2 heads
    local_bh = (B // 2) * (H // 2)
    for line in calls:
        operands = line.split("custom-call(", 1)[1]
        shapes = set(re.findall(r"bf16\[(\d+),(\d+),(\d+)\]", operands))
        assert shapes == {(str(local_bh), str(L), str(Dh))}, line


def _custom_calls(hlo):
    return [
        line for line in hlo.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]


def _scoped_vmem(call):
    """(stated, used) bytes of a Mosaic call's scoped VMEM, as the compiled
    text has them."""
    return tuple(int(re.search(rf'"{key}":\[\{{[^}}]*"size":"(\d+)"', call).group(1))
                 for key in ("scoped_memory_configs", "used_scoped_memory_configs"))


def _expert_calls(calls):
    """How many of the Mosaic calls are the sparse MLP's one kernel under
    its `moe/experts` scope, each stating a VMEM limit the chip has (v5e:
    128 MiB a core; inside a whole program the compiler also counts what it
    places in VMEM around the call, so what is USED is held against the
    stated limit where the kernel is compiled alone)."""
    mine = [c for c in calls if re.search(r'op_name="[^"]*/moe/experts/[^"]*grouped_swiglu', c)]
    assert all(_scoped_vmem(call)[0] <= 100 << 20 for call in mine)
    return len(mine)


def _cell_as_the_engine_builds_it(topo, monkeypatch, name):
    """(config, engine parameters, model, abstract parameters, `sd`, `placed`)
    of a serve cell: the model from the configuration's glue at its published
    widths, everything placed on one described v5e chip."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from bench_matrix import modelglue, spec

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")  # TPU target, CPU process
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    placed = lambda tree: jax.tree_util.tree_map(lambda a: sd(a.shape, a.dtype), tree)
    cell = spec.load_cell(name)
    config, eng = cell["config"], cell["traffic"]["engine"]
    model = modelglue.build_model(config, eng["max_seq_len"], remat=False)
    params = placed(jax.eval_shape(
        modelglue.init_fn(model, config), jax.random.PRNGKey(0))["params"])
    return config, eng, model, params, sd, placed


def _held(m):
    """Bytes a call holds by the compiler's `memory_analysis()`: arguments,
    temporaries and the outputs that alias no argument."""
    return m.argument_size_in_bytes + m.temp_size_in_bytes + (
        m.output_size_in_bytes - m.alias_size_in_bytes)


def _call_names(calls):
    """The instructions' names without their numbers, sorted."""
    return sorted(re.search(r"%(\w+?)(\.\d+)? = ", line).group(1) for line in calls)


@pytest.mark.parametrize("BH", [64, 32])
def test_flash_kernels_compile_at_the_train_cells_shape(topo, monkeypatch, BH):
    """Forward and the one-pass backward at what the train cells run (L 4096,
    Dh 128, bf16, causal; 64 rows of (batch, head) a chip in `lm_train_1chip`,
    32 in `lm_fsdp_4chip`) with the tuned table's blocks: Mosaic takes them
    inside the scoped VMEM the call asks for, q and dO whole beside dQ's fp32
    accumulator, and the custom calls carry the kernels' names."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pytorch_distributed_example_tpu.ops.flash_attention import (
        _use_streaming, flash_with_lse, resolved_block_sizes,
    )

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")
    L, D = 4096, 128
    bq, bk = resolved_block_sizes(L)
    assert not _use_streaming(L, D, 2)
    x = jax.ShapeDtypeStruct(
        (BH, L, D), jnp.bfloat16, sharding=SingleDeviceSharding(topo.devices[0])
    )

    def loss(q, k, v):
        o, lse = flash_with_lse(q, k, v, D ** -0.5, True, bq, bk, False)
        return o.astype(jnp.float32).sum() + lse.sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x).compile().as_text()
    calls = _custom_calls(hlo)
    assert len(calls) == 2
    # the instruction takes the innermost scope: the kernel's name, here
    # inside `jvp(...)` and its transpose (in a train step: `flash_fwd.3`)
    names = _call_names(calls)
    assert len(names) == 2 and "flash_fwd" in names[0] and "flash_bwd" in names[1], names


@pytest.mark.parametrize("B", [32, 256], ids=["32_rows", "256_rows"])
def test_paged_decode_kernel_compiles_at_mistral_widths(topo, monkeypatch, B):
    """`ops.paged_decode_attention` at the serve cells' shape (32 rows, 32
    query heads over 8 KV heads of 128, 4096 pages of 16 tokens, 512-entry
    tables) goes through Mosaic for a v5e, and the (num_blocks, bs * KV,
    Dh) view it reads the pools through is a bitcast: a layout change
    would copy the whole pool in every layer. At 256 rows, the most whose
    scalars fit, every row's waiting softmax state is past the VMEM a
    kernel gets unasked: the call asks for what it holds
    (`_shared_vmem_limit`)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pytorch_distributed_example_tpu.ops import paged_decode_attention, paged_kernel

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")  # TPU target, CPU process
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    H, KV, Dh, bs, nblk, nb = 32, 8, 128, 16, 4096, 512
    pool = sd((nblk, bs, KV, Dh), jnp.bfloat16)
    assert paged_kernel(1, pool, sd((B, nb), jnp.int32)) == "decode"
    hlo = jax.jit(paged_decode_attention).lower(
        sd((B, H, Dh), jnp.bfloat16), pool, pool,
        sd((B, nb), jnp.int32), sd((B,), jnp.int32),
    ).compile().as_text()
    (call,) = _custom_calls(hlo)
    assert f"bf16[{B},{H},{Dh}]" in call.split("custom-call(")[0]
    pool_ops = [
        line for line in hlo.splitlines()
        if f" = bf16[{nblk},{bs * KV},{Dh}]" in line
    ]
    assert len(pool_ops) == 2 and all(" bitcast(" in l for l in pool_ops)


def test_paged_decode_kernel_reads_a_30_head_model_s_pool_without_a_copy(topo, monkeypatch):
    """30 KV heads (a query group of one): the device pads a (30, 128)
    plane to 32 rows, so over a pool of 30 heads the kernel's (num_blocks,
    bs * KV, Dh) view is a COPY of the pool in every call (eight 480 MB
    copies a step: refused for memory). `pool_kv_heads` gives the pool 32,
    and then the view is a bitcast again."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pytorch_distributed_example_tpu.ops.paged_attention import pool_kv_heads
    from pytorch_distributed_example_tpu.ops import paged_decode_attention

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")  # TPU target, CPU process
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    B, Dh, bs, nblk, nb = 32, 128, 16, 4096, 512
    assert pool_kv_heads(30) == 32 and pool_kv_heads(8) == 8 and pool_kv_heads(2) == 2
    copies = {}
    for KV in (30, pool_kv_heads(30)):
        pool = sd((nblk, bs, KV, Dh), jnp.bfloat16)
        hlo = jax.jit(paged_decode_attention).lower(
            sd((B, KV, Dh), jnp.bfloat16), pool, pool,
            sd((B, nb), jnp.int32), sd((B,), jnp.int32),
        ).compile().as_text()
        assert len(_custom_calls(hlo)) == 1
        views = [l for l in hlo.splitlines() if f" = bf16[{nblk},{bs * KV},{Dh}]" in l]
        copies[KV] = sum(" bitcast(" not in l for l in views)
    assert copies == {30: 2, 32: 0}


def test_the_recurrence_kernel_compiles_at_the_hybrid_cell_s_widths(topo, monkeypatch):
    """`ops.delta_recurrence.paged_delta_step` at the hybrid serve cell's
    shape (32 rows over a pool of 32 state blocks of 30 heads x 96 x 192
    float32) goes through Mosaic for a v5e, with the pool aliased from its
    input to its output: no copy of it, no temporary."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pytorch_distributed_example_tpu.ops.delta_recurrence import (
        delta_kernel_ok, paged_delta_step)

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")  # TPU target, CPU process
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    nblk, B, H, dk, dv = 32, 32, 30, 96, 192
    pool = sd((nblk, H, dk, dv), jnp.float32)
    assert delta_kernel_ok(pool)
    vec = lambda d: sd((B, H, d), jnp.float32)
    compiled = jax.jit(paged_delta_step, donate_argnums=(0,)).lower(
        pool, sd((B,), jnp.int32), sd((B,), jnp.bool_), vec(dk), vec(dk), vec(dv),
        sd((B, H), jnp.float32), sd((B, H), jnp.float32),
    ).compile()
    (call,) = _custom_calls(compiled.as_text())
    assert "paged_delta_step" in call
    mem = compiled.memory_analysis()
    nbytes = nblk * H * dk * dv * 4
    assert mem.alias_size_in_bytes >= nbytes and mem.temp_size_in_bytes < nbytes // 8


def test_the_recurrence_kernel_compiles_with_a_decay_a_state_row(topo, monkeypatch):
    """`paged_delta_step` with a VECTOR decay (alpha: (B, H, dk)) at the
    long-context cell's shape: 16 rows over a pool of 16 state blocks of 64
    heads x 128 x 128 float32; the decay rides down the sublanes beside k
    and q, the pool is aliased as in the scalar form."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pytorch_distributed_example_tpu.ops.delta_recurrence import (
        delta_kernel_ok, paged_delta_step)

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")  # TPU target, CPU process
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    nblk, B, H, dk, dv = 16, 16, 64, 128, 128
    pool = sd((nblk, H, dk, dv), jnp.float32)
    assert delta_kernel_ok(pool)
    vec = lambda d: sd((B, H, d), jnp.float32)
    compiled = jax.jit(paged_delta_step, donate_argnums=(0,)).lower(
        pool, sd((B,), jnp.int32), sd((B,), jnp.bool_), vec(dk), vec(dk), vec(dv),
        vec(dk), sd((B, H), jnp.float32),
    ).compile()
    (call,) = _custom_calls(compiled.as_text())
    assert "paged_delta_step" in call
    mem = compiled.memory_analysis()
    nbytes = nblk * H * dk * dv * 4
    assert mem.alias_size_in_bytes >= nbytes and mem.temp_size_in_bytes < nbytes // 8


def test_the_longctx_cell_s_step_and_chunks_fit_the_chip_beside_its_pool(topo, monkeypatch):
    """`serve_solar_longctx_c16` as the engine builds it: the model from the
    configuration's glue at its published widths, 16 slots, tables of 2048
    pages, a 32768-block K/V pool of ONE softmax layer and a 16-block state
    pool of three linear layers (64 x 128 x 128 float32 and a 3-token tail).
    The step and the three chunk buckets compile for v5e: the recurrence
    kernel in every linear layer of the step (the chunk's scan is XLA's), the
    paged decode / chunk kernel in the softmax layer, the grouped kernel in
    every sparse MLP (1280-wide experts in tiles of 256), the donated pools
    updated in place, and what a call holds is under the chip's 16 GB."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.serve.cache import init_paged_cache
    from pytorch_distributed_example_tpu.serve.decode import paged_programs

    config, eng, model, params, sd, placed = _cell_as_the_engine_builds_it(
        topo, monkeypatch, "serve_solar_longctx_c16")
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))
    assert weights == pytest.approx(6.62e9, rel=2e-3)
    S, bs = eng["slots"], eng["block_size"]
    nb = eng["max_seq_len"] // bs
    assert nb == 2048
    tree = placed(jax.eval_shape(
        lambda: init_paged_cache(model, eng["pool_blocks"], bs, state_blocks=S)))
    assert tree["layers_0"]["attn"]["k"].shape == (eng["pool_blocks"], bs, 8, 128)
    assert tree["layers_1"]["linear_attn"]["state"].shape == (S, 64, 128, 128)
    assert tree["layers_1"]["linear_attn"]["conv"].shape == (S, 3, 3 * 64 * 128)
    pools = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(tree))
    assert pools == pytest.approx(2.15e9 + 0.21e9, rel=5e-3)
    chunk, _, _, step = paged_programs(model, 0.0, None)
    tables = lambda rows: (sd((rows, nb), jnp.int32), sd((rows, 1), jnp.int32))
    lowered = {"step": step.lower(params, tree, sd((S,), jnp.int32), sd((S,), jnp.int32),
                                  sd((S, 2), jnp.uint32), tables(S))}
    for C in (128, 256, eng["prefill_chunk_tokens"]):
        lowered[f"chunk{C}"] = chunk.lower(
            params, tree, sd((1, C), jnp.int32), tables(1), sd((), jnp.int32))
    for name, low in lowered.items():
        compiled = low.compile()
        calls = _custom_calls(compiled.as_text())
        kernel = "paged_decode_attention" if name == "step" else "paged_chunk_attention"
        assert sum(kernel in c for c in calls) == 1, name
        assert sum("paged_delta_step" in c for c in calls) == (3 if name == "step" else 0), name
        # a chunk's sparse layer holds the kernel in both branches of
        # `_share_of_assignments` (a share of the rows, or every one)
        assert sum("grouped_swiglu" in c for c in calls) == (4 if name == "step" else 8), name
        if name == "step":
            assert _expert_calls(calls) == 4
        assert "ragged-dot" not in compiled.as_text(), name
        m = compiled.memory_analysis()
        assert m.alias_size_in_bytes >= pools  # both pools are written in place
        held = _held(m)
        assert held < 10.0e9 < 16e9, (name, held)


@pytest.mark.parametrize("heads,window,nblk", [(48, None, 16384), (64, 512, 2112)])
def test_paged_decode_kernel_compiles_at_the_patterned_cell_s_widths(
    topo, monkeypatch, heads, window, nblk
):
    """The same kernel as `serve_laguna_mixed_c32` calls it: 48 query heads
    over the full layers' 16384-block pool, 64 with a 512-key window over
    the window layers' 2112 blocks, 8 KV heads of 128, 32 rows."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pytorch_distributed_example_tpu.ops import paged_decode_attention

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")  # TPU target, CPU process
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    B, KV, Dh, bs, nb = 32, 8, 128, 16, 512
    pool = sd((nblk, bs, KV, Dh), jnp.bfloat16)
    hlo = jax.jit(functools.partial(paged_decode_attention, window=window)).lower(
        sd((B, heads, Dh), jnp.bfloat16), pool, pool,
        sd((B, nb), jnp.int32), sd((B,), jnp.int32),
    ).compile().as_text()
    (call,) = _custom_calls(hlo)
    assert f"bf16[{B},{heads},{Dh}]" in call.split("custom-call(")[0]


@pytest.mark.parametrize("L,heads", [(512, 32), (128, 32), (512, 48)])
def test_paged_chunk_kernel_compiles_at_the_serve_cells_widths(
    topo, monkeypatch, L, heads
):
    """`ops.paged_chunk_attention` as the serve cells' `prefill_chunk`
    calls it (one row, the largest and smallest bucket, 32 query heads
    and the patterned cell's 48 over 8 KV heads of 128): Mosaic takes the
    strided 32-bit reads of a bfloat16 buffer and the VMEM the kernel
    asks for, under a name the decode rooflines' pattern does not match,
    and the pools still go in as bitcasts."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pytorch_distributed_example_tpu.ops import paged_chunk_attention

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")  # TPU target, CPU process
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    KV, Dh, bs, nblk, nb = 8, 128, 16, 4096, 512
    pool = sd((nblk, bs, KV, Dh), jnp.bfloat16)
    hlo = jax.jit(paged_chunk_attention).lower(
        sd((1, L, heads, Dh), jnp.bfloat16), pool, pool,
        sd((1, nb), jnp.int32), sd((1,), jnp.int32),
    ).compile().as_text()
    (call,) = _custom_calls(hlo)
    assert "paged_chunk_attention" in call
    assert "paged_decode_attention" not in call
    pool_ops = [
        line for line in hlo.splitlines()
        if f" = bf16[{nblk},{bs * KV},{Dh}]" in line
    ]
    assert len(pool_ops) == 2 and all(" bitcast(" in l for l in pool_ops)


# (blocks of the pool, rows of a step, heads): the long-document cell and the
# agent cell
LATENT_CELLS = {"pangu_longdoc_c8": (8192, 8, 128), "xing_agent_prefix_c32": (16384, 32, 32)}


@pytest.mark.parametrize("L", [1, 512, 128, 2048])
@pytest.mark.parametrize("cell", sorted(LATENT_CELLS))
def test_the_latent_kernels_compile_at_the_latent_cells_shapes(topo, monkeypatch, cell, L):
    """`ops.latent_decode_attention` and `ops.latent_chunk_attention` as
    `serve_pangu_longdoc_c8` calls them (8 rows x 1024 pages of the
    8192-block latent pool for the step, 128 heads) and as
    `serve_xing_agent_prefix_c32` does (32 rows x 1024 pages of 16384 blocks,
    32 heads, the softmax scale with YaRN's factor): one row for a chunk (the
    largest and the smallest bucket; the layer's own queries, 128 + 64 values
    a head, and its two up-projections: the kernel makes a key's heads in
    VMEM), rows of 576 values held as 640, of which 512 are values. Mosaic
    takes the page copies (it refused a pool told 576: `Slice shape along
    dimension 2 must be aligned to tiling (128)`), the 640-wide contraction
    of a step, the chunk kernel's head group in VMEM, and the pool goes into
    the call as it is: no copy of its 1.17 or 2.68 GB in any call."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pytorch_distributed_example_tpu.ops import (
        latent_chunk_attention, latent_decode_attention, paged_kernel, pool_latent_width,
    )

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")  # TPU target, CPU process
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    (nblk, rows, H), bs, nb, rank = LATENT_CELLS[cell], 16, 1024, 512
    dn, dr, dv = 128, 64, 128
    W = pool_latent_width(rank + dr)
    assert W == 640
    pool = sd((nblk, bs, W), jnp.bfloat16)
    B = rows if L == 1 else 1
    tables = sd((B, nb), jnp.int32)
    if L == 1:
        assert paged_kernel(1, pool, tables, rank=rank) == "latent_decode"
        call, name = functools.partial(latent_decode_attention, rank=rank), "latent_decode"
        operands = (sd((B, H, W), jnp.bfloat16),)
    else:
        # the chunk's queries as the layer makes them, and its up-projections
        assert paged_kernel(L, pool, tables, rank=rank) == "latent_chunk"
        # and the two halves of its `kv_b_proj`, sliced as the layer slices them
        def call(q_nope, q_rope, w_kvb, pool, tables, starts, scale):
            w_kvb = w_kvb.reshape(rank, H, dn + dv)
            return latent_chunk_attention(
                q_nope, q_rope, w_kvb[..., :dn], w_kvb[..., dn:], pool, tables, starts, scale)

        name = "latent_chunk"
        operands = (sd((B, L, H, dn), jnp.bfloat16), sd((B, L, H, dr), jnp.bfloat16),
                    sd((rank, H * (dn + dv)), jnp.bfloat16))
    scale = 192 ** -0.5 * (2.0047 if H == 32 else 1.0)
    compiled = jax.jit(functools.partial(call, scale=scale)).lower(
        *operands, pool, tables, sd((B,), jnp.int32)).compile()
    hlo = compiled.as_text()
    (custom,) = _custom_calls(hlo)
    assert f"{name}_attention" in custom
    # the kernel's scope holds the call alone: the share of a roofline tells
    # a whole run of the program by one operation a layer under it
    assert [l for l in hlo.splitlines() if f"/{name}_kernel/" in l and " = " in l] == [custom]
    # the pool is an operand of the call itself, and nothing else has its shape
    assert "%pool" in custom.split("custom-call(")[1]
    assert not [l for l in hlo.splitlines()
                if f" = bf16[{nblk},{bs},{W}]" in l and "parameter(" not in l]
    if L > 1:  # and so is the layer's `kv_b_proj`, whole: no copy lays it out
        assert not [l for l in hlo.splitlines() if f" = bf16[{rank},{H * (dn + dv)}]" in l
                    and (" copy(" in l or " fusion(" in l)]
    # a chunk's temporaries are its queries and outputs regrouped by head
    # (2 x 512 x 128 x (128 + 64 + 128) x 2 B = 84 MB where the absorbed form
    # held 151), never the pool's 168 MB nor a key's up-projected heads
    width = W + rank if L == 1 else dn + dr + dv
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * L * H * width * 2 + 2**20


def test_the_agent_cell_s_step_and_chunk_fit_the_chip_beside_its_pool(topo, monkeypatch):
    """`serve_xing_agent_prefix_c32` as the engine builds it: the model from
    the configuration's glue at its published widths, 32 slots, tables of
    1024 pages, the 16384-block latent pool. The step and the 512-token chunk
    compile for v5e with both latent kernels inside, the donated pool is
    updated in place (no second 2.68 GB), the six sparse layers' grouped
    products are one Mosaic call each, and what a call holds (weights
    11.33 GB + pool 2.68 + its temporaries and, for a chunk, 0.27 GB of
    logits) is under the chip's 16 GB."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.serve.cache import init_paged_cache
    from pytorch_distributed_example_tpu.serve.decode import paged_programs

    config, eng, model, params, sd, placed = _cell_as_the_engine_builds_it(
        topo, monkeypatch, "serve_xing_agent_prefix_c32")
    S, bs, C = eng["slots"], eng["block_size"], eng["prefill_chunk_tokens"]
    nb = eng["max_seq_len"] // bs
    tree = placed(jax.eval_shape(lambda: init_paged_cache(model, eng["pool_blocks"], bs)))
    pool = eng["pool_blocks"] * bs * 640 * 2 * config["num_hidden_layers"]
    assert pool == pytest.approx(2.68e9, rel=2e-3)
    chunk, _, _, step = paged_programs(model, 0.0, None)
    lowered = {
        "step": step.lower(params, tree, sd((S,), jnp.int32), sd((S,), jnp.int32),
                           sd((S, 2), jnp.uint32), sd((S, nb), jnp.int32)),
        "chunk": chunk.lower(params, tree, sd((1, C), jnp.int32), sd((1, nb), jnp.int32),
                             sd((), jnp.int32)),
    }
    for name, low in lowered.items():
        compiled = low.compile()
        calls = _custom_calls(compiled.as_text())
        kernel = "latent_decode_attention" if name == "step" else "latent_chunk_attention"
        assert sum(kernel in c for c in calls) == config["num_hidden_layers"], name
        # ONE Mosaic call a sparse layer holds its three grouped products
        assert _expert_calls(calls) == 6 and "ragged-dot" not in compiled.as_text(), name
        m = compiled.memory_analysis()
        assert m.argument_size_in_bytes == pytest.approx(14.016e9, rel=2e-3)
        assert m.alias_size_in_bytes >= pool  # the pool is written in place
        held = _held(m)
        assert held < 14.5e9 < 16e9, (name, held)


def test_the_assist_cell_s_step_and_chunks_fit_the_chip_beside_its_pool(topo, monkeypatch):
    """`serve_lfm2_assist_c64` as the engine builds it: the model from the
    configuration's glue at its published widths, 64 slots, tables of 256
    pages, the 16384-block K/V pool of 6 attention layers held as (blocks,
    16, 4, 128) (two 64-wide heads a lane row), a 64-block state pool of 18
    tails. The step and the three chunk buckets compile for v5e with the
    paged decode / chunk kernel in every attention layer and the three
    grouped products of every sparse layer as ONE Mosaic call (the expert
    width 1792 as one tile: `ops.grouped_mlp.swiglu_tile`), the donated pools
    are updated in place, and what a call holds is under the chip's 16 GB."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.serve.cache import init_paged_cache
    from pytorch_distributed_example_tpu.serve.decode import paged_programs

    config, eng, model, params, sd, placed = _cell_as_the_engine_builds_it(
        topo, monkeypatch, "serve_lfm2_assist_c64")
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(params))
    assert weights == pytest.approx(8.93e9, rel=2e-3)
    S, bs = eng["slots"], eng["block_size"]
    nb = eng["max_seq_len"] // bs
    tree = placed(jax.eval_shape(
        lambda: init_paged_cache(model, eng["pool_blocks"], bs, state_blocks=S)))
    assert tree["layers_2"]["attn"]["k"].shape == (eng["pool_blocks"], bs, 4, 128)
    assert tree["layers_0"]["gated_conv"]["tail"].shape == (S, 2, 2048)
    pool = eng["pool_blocks"] * bs * 8 * 64 * 2 * 2 * 6
    assert pool == pytest.approx(3.22e9, rel=2e-3)
    chunk, _, _, step = paged_programs(model, 0.0, None)
    tables = lambda rows: (sd((rows, nb), jnp.int32), sd((rows, 1), jnp.int32))
    lowered = {"step": step.lower(params, tree, sd((S,), jnp.int32), sd((S,), jnp.int32),
                                  sd((S, 2), jnp.uint32), tables(S))}
    for C in (128, 256, eng["prefill_chunk_tokens"]):
        lowered[f"chunk{C}"] = chunk.lower(
            params, tree, sd((1, C), jnp.int32), tables(1), sd((), jnp.int32))
    for name, low in lowered.items():
        compiled = low.compile()
        calls = _custom_calls(compiled.as_text())
        kernel = "paged_decode_attention" if name == "step" else "paged_chunk_attention"
        assert sum(kernel in c for c in calls) == 6, name
        assert _expert_calls(calls) == 22, name
        assert "ragged-dot" not in compiled.as_text(), name
        m = compiled.memory_analysis()
        assert m.alias_size_in_bytes >= pool  # the pool is written in place
        held = _held(m)
        assert held < 13.5e9 < 16e9, (name, held)


@pytest.mark.parametrize("rows", [32, 128, 256, 512])
def test_the_grouped_expert_kernel_compiles_and_keeps_its_scope(topo, monkeypatch, rows):
    """The sparse MLP as `serve_laguna_mixed_c32` runs it (256 experts of
    2048 x 512 in bfloat16, top 8; a decode step's 32 rows and the three
    chunk buckets): the three grouped products are ONE Mosaic call for v5e
    that keeps `moe/experts` in its path, which the cell's MoE metrics
    read. (`jax.lax.ragged_dot` compiles too, to XLA's own `ragged-dot-none`
    call with no path: three quarters of the step would read as unscoped.)"""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pytorch_distributed_example_tpu.parallel.expert_parallel import (
        dropless_moe,
        grouped_kernel_ok,
    )

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")  # TPU target, CPU process
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    D, F, E, K = 2048, 512, 256, 8
    assert grouped_kernel_ok(rows * K, D, F, jnp.bfloat16)

    def moe(x, router, w_gate, w_up, w_down, mask):
        with jax.named_scope("moe"):
            return dropless_moe(x, router, w_gate, w_up, w_down, n_experts=E, top_k=K,
                                scale=2.5, row_mask=mask)

    hlo = jax.jit(moe).lower(
        sd((rows, D), jnp.bfloat16), sd((D, E), jnp.bfloat16),
        sd((E, D, F), jnp.bfloat16), sd((E, D, F), jnp.bfloat16),
        sd((E, F, D), jnp.bfloat16), sd((rows,), jnp.bool_),
    ).compile().as_text()
    calls = _custom_calls(hlo)
    assert len(calls) == 1 and _expert_calls(calls) == 1 and "ragged-dot" not in hlo


def test_the_grouped_expert_kernel_compiles_at_the_widest_experts(topo, monkeypatch):
    """The kernel alone at `serve_pangu_longdoc_c8`'s experts, 7680 x 2048:
    the one shape of the four configurations whose expert is more than one
    tile (`swiglu_tile`: 512 of the 2048, copies of 7.5 MiB), the 512 rows a
    chunk's `_share_of_assignments` hands it over the 8 held experts, and
    the most VMEM any of them states."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pytorch_distributed_example_tpu.ops.grouped_mlp import swiglu_tile, vmem_limit
    from pytorch_distributed_example_tpu.parallel.expert_parallel import (
        grouped_kernel_ok,
        grouped_swiglu,
    )

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")  # TPU target, CPU process
    one = SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    rows, D, F, E = 512, 7680, 2048, 8
    assert grouped_kernel_ok(rows, D, F, jnp.bfloat16) and swiglu_tile(D, F, 2) == 512
    assert vmem_limit(D, 512, 2) == max(
        vmem_limit(d, swiglu_tile(d, f, 2), 2)
        for d, f in ((2048, 512), (2048, 1792), (3584, 1024), (D, F)))
    hlo = jax.jit(grouped_swiglu).lower(
        sd((rows, D), jnp.bfloat16), sd((E, D, F), jnp.bfloat16), sd((E, D, F), jnp.bfloat16),
        sd((E, F, D), jnp.bfloat16), sd((E,), jnp.int32),
    ).compile().as_text()
    calls = _custom_calls(hlo)
    assert len(calls) == 1 and "grouped_swiglu" in calls[0] and "ragged-dot" not in hlo
    stated, used = _scoped_vmem(calls[0])
    assert used <= stated == vmem_limit(D, 512, 2) <= 100 << 20


def test_the_patterned_cell_s_step_and_chunk_hold_one_expert_call_a_layer(topo, monkeypatch):
    """`serve_laguna_mixed_c32` as the engine builds it: the model from the
    configuration's glue at its published widths, 32 slots, the full pool of
    16384 blocks beside the window pool of 32 x 66. The step and the
    512-token chunk compile for v5e with ONE Mosaic call under
    `moe/experts` in each of the four sparse layers and no `ragged-dot`,
    and what a call holds is under the chip's 16 GB."""
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_example_tpu.serve.cache import init_paged_cache
    from pytorch_distributed_example_tpu.serve.decode import paged_programs

    config, eng, model, params, sd, placed = _cell_as_the_engine_builds_it(
        topo, monkeypatch, "serve_laguna_mixed_c32")
    S, bs, C = eng["slots"], eng["block_size"], eng["prefill_chunk_tokens"]
    nb = eng["max_seq_len"] // bs
    tree = placed(jax.eval_shape(lambda: init_paged_cache(
        model, eng["pool_blocks"], bs, window_blocks=S * 66)))
    assert model.cfg.cache_kinds == ("full", "window")
    tables = lambda rows: (sd((rows, nb), jnp.int32), sd((rows, nb), jnp.int32))
    chunk, _, _, step = paged_programs(model, 0.0, None)
    lowered = {
        "step": step.lower(params, tree, sd((S,), jnp.int32), sd((S,), jnp.int32),
                           sd((S, 2), jnp.uint32), tables(S)),
        "chunk": chunk.lower(params, tree, sd((1, C), jnp.int32), tables(1),
                             sd((), jnp.int32)),
    }
    for name, low in lowered.items():
        compiled = low.compile()
        assert _expert_calls(_custom_calls(compiled.as_text())) == 4, name
        assert "ragged-dot" not in compiled.as_text(), name
        m = compiled.memory_analysis()
        held = _held(m)
        assert held < 11.5e9 < 16e9, (name, held)


def test_tp2_paged_decode_step_compiles_under_mesh(topo, monkeypatch):
    """A tp=2 engine's decode step: GSPMD partitions everything but the
    decode attention kernel, which the step's `ops.partitioned_over`
    context runs per device on its KV-head shard — q split on heads,
    the pool on KV heads, tables and lengths whole. On the CPU mesh the kernel is
    interpreted and GSPMD partitions it like any HLO, so only this
    compile sees the custom call under the mesh."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pytorch_distributed_example_tpu.models import (
        TransformerConfig,
        TransformerLM,
    )
    from pytorch_distributed_example_tpu.models.transformer import (
        sharding_rules,
    )
    from pytorch_distributed_example_tpu.parallel import sharding as shd
    from pytorch_distributed_example_tpu.parallel.tensor_parallel import (
        kv_pool_spec,
    )
    from pytorch_distributed_example_tpu.serve.cache import init_paged_cache
    from pytorch_distributed_example_tpu.serve.decode import paged_programs

    monkeypatch.setenv("TDX_FLASH_INTERPRET", "0")
    mesh = Mesh(np.array(topo.devices[:2]), ("tp",))
    S, H, KV, Dh, bs, nblk, M = 8, 4, 2, 128, 16, 64, 512
    cfg = TransformerConfig(
        vocab_size=2048, d_model=H * Dh, n_layers=2, n_heads=H,
        n_kv_heads=KV, d_ff=1024, max_seq_len=M, dtype=jnp.bfloat16,
    )
    model = TransformerLM(cfg)
    params = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1, 8), jnp.int32)),
        jax.random.PRNGKey(0),
    )["params"]
    specs = shd.make_param_specs(
        params, sharding_rules(tp_axis="tp", fsdp_axis=None), mesh
    )
    sd = lambda l, s: jax.ShapeDtypeStruct(
        l.shape, l.dtype, sharding=NamedSharding(mesh, s)
    )
    tree = jax.eval_shape(lambda: init_paged_cache(model, nblk, bs))
    whole = lambda shape, dt: sd(jax.ShapeDtypeStruct(shape, dt), P())
    prefill_chunk, _, _, step = paged_programs(model, 0.0, None, mesh, "tp")
    params_in = jax.tree_util.tree_map(sd, params, specs)
    tree_in = jax.tree_util.tree_map(
        lambda l: sd(l, kv_pool_spec(l, mesh, "tp")), tree
    )
    hlo = step.lower(
        params_in, tree_in,
        whole((S,), jnp.int32), whole((S,), jnp.int32),
        whole((S, 2), jnp.uint32), whole((S, M // bs), jnp.int32),
    ).compile().as_text()
    calls = _custom_calls(hlo)
    assert len(calls) == cfg.n_layers
    for line in calls:
        out, operands = line.split("custom-call(", 1)
        assert f"bf16[{S},{H // 2},{Dh}]" in out, line
        pools = re.findall(rf"bf16\[{nblk},(\d+),{Dh}\]", operands)
        assert pools == [str(bs * KV // 2)] * 2, line
    # a prefill chunk of ONE token is a decode call to the model: it
    # takes the kernel inside the same context (outside it, the compiler
    # refuses: "Mosaic kernels cannot be automatically partitioned")
    hlo = prefill_chunk.lower(
        params_in, tree_in, whole((1, 1), jnp.int32),
        whole((1, M // bs), jnp.int32), whole((), jnp.int32),
    ).compile().as_text()
    assert len(_custom_calls(hlo)) == cfg.n_layers
    # and a chunk of 16 tokens the chunk kernel, on one KV head a device
    hlo = prefill_chunk.lower(
        params_in, tree_in, whole((1, 16), jnp.int32),
        whole((1, M // bs), jnp.int32), whole((), jnp.int32),
    ).compile().as_text()
    calls = _custom_calls(hlo)
    assert len(calls) == cfg.n_layers
    assert all("paged_chunk_attention" in line for line in calls)
