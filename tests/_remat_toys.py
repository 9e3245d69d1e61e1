"""Toy models and trainers the remat-ladder tests share
(`test_remat_ladder.py`: the fit, the names, the lowered step;
`test_remat_ladder_trainers.py`: every rung against no remat)."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax

import pytorch_distributed_example_tpu as tdx
from pytorch_distributed_example_tpu.models.transformer import (
    LayerSpec,
    RopeSpec,
    TransformerConfig,
    TransformerLM,
)
from pytorch_distributed_example_tpu.utils import remat


def pattern_cfg(**kw):
    full = RopeSpec(5e5, 0.5, (8.0, 16, 64.0, 1.0, 1.4))
    return TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=3, d_ff=48,
        max_seq_len=64, head_size=16, window=8, attn_gate=True,
        rope_pairs="halves", sparse_experts=4, sparse_top_k=2, sparse_d_ff=16,
        shared_d_ff=16, routed_scale=2.5,
        layers=(LayerSpec("full", 4, full, "dense"),
                LayerSpec("window", 8, RopeSpec(1e4), "sparse"),
                LayerSpec("full", 4, full, "sparse")), **kw)


def dense_cfg(**kw):
    return TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=3, d_ff=48,
        max_seq_len=64, use_flash=True, **kw)


MODELS = {"dense_gqa": dense_cfg, "patterned": pattern_cfg}
SEQ = 32
LADDER = remat.LADDER  # whole, whatever a test cuts it to


def loss(logits, y):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], y[:, 1:]).mean()


def build(kind, trainer, remat_on, world):
    """(model config, step, params, opt_state, batch, lower) of one trainer
    over a toy model; `lower()` gives the step's `Lowered`."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)  # a window layer is dense
        model = TransformerLM(MODELS[kind](remat=remat_on))
        variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    if trainer == "fsdp":
        from pytorch_distributed_example_tpu.mesh import init_device_mesh
        from pytorch_distributed_example_tpu.models import transformer_sharding_rules
        from pytorch_distributed_example_tpu.parallel import fully_shard

        rows = 4
        mesh = init_device_mesh(("fsdp", "tp"), (4, 1), devices=jax.devices()[:4])
        mod = fully_shard(
            model, variables, mesh, axis="fsdp",
            rules=transformer_sharding_rules("tp", "fsdp"), data_axes=("fsdp",))
        step = mod.make_train_step(optax.sgd(1.0), loss)
        params = mod.params
    else:
        group = tdx.new_group([0]) if trainer == "ddp_world1" else world
        rows = group.size()
        ddp = tdx.DistributedDataParallel(model, variables, process_group=group)
        step = ddp.make_train_step(optax.sgd(1.0), loss)
        params = ddp.params
    x = jax.random.randint(jax.random.PRNGKey(1), (rows, SEQ), 0, 64)
    opt_state = step.init_opt_state(params)

    def lower():
        if trainer == "fsdp":
            return step.lower(params, opt_state, x, x)
        if step._jitted is None:  # under ZeRO the program is built at first dispatch
            step(jax.tree_util.tree_map(jnp.copy, params), opt_state, x, x)
        return step._jitted.lower(params, opt_state, {}, x, x, jax.random.PRNGKey(0))

    return model.cfg, step, params, opt_state, x, lower


def force(monkeypatch, rung):
    """Every step built from here on takes rung `rung`: the devices report
    a limit nothing reaches, and the ladder ends at that rung."""
    monkeypatch.setattr(remat, "LADDER", LADDER[: rung + 1])
    monkeypatch.setattr(remat, "device_limit_bytes", lambda devices: 10**15)


def run(step, params, opt_state, x):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        new_params, _, loss = step(params, opt_state, x, x)
    flat = np.concatenate([
        np.asarray(a, np.float32).ravel()
        for a in jax.tree_util.tree_leaves(new_params)])
    return float(loss), flat
