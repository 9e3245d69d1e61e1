"""Builds the system's model from a configuration file, makes its weights
on the device from the seed, and hands the plain reference one layer of the
system's parameter tree at a time. The only file that knows both the
configuration file's key names and the program's parameter names."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import spec

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def build_model(config: dict, max_seq_len: int, remat: bool):
    """The program's model object at the configuration's sizes."""
    m = config["model"]
    kw = {ours: config[theirs] for ours, theirs in m["keywords"].items()}
    kw.update(m.get("fixed", {}))
    if config["head_dim"] * config["num_attention_heads"] != config["hidden_size"]:
        raise spec.SpecError("the program derives head_dim as d_model / n_heads")
    cfg = spec.resolve(m["config"])(
        max_seq_len=max_seq_len, remat=remat,
        dtype=DTYPES[config["dtype"]["activations"]], **kw,
    )
    return spec.resolve(m["factory"])(cfg)


def init_fn(model, config: dict):
    """key -> variables, every leaf cast to the serving/training dtype
    inside the same program, so that under jit no float32 copy of the whole
    tree outlives its leaf."""
    dtype = DTYPES[config["dtype"]["weights"]]

    def init(key):
        variables = model.init(key, jnp.zeros((1, 8), jnp.int32))
        return jax.tree_util.tree_map(lambda x: x.astype(dtype), variables)

    return init


def make_variables(model, config: dict, seed: int, out_shardings=None):
    """Variables made on the device in one jitted call, already in the
    layout `out_shardings` names (None: the default device)."""
    init = jax.jit(init_fn(model, config), out_shardings=out_shardings)
    return init(jax.random.PRNGKey(seed))


def reference_parts(variables, device=None):
    """(embedding, layer iterator, final norm, output matrix) in the plain
    reference's own names, each layer moved to `device` only when asked
    for, so a sharded tree is gathered one layer at a time."""
    p = variables["params"] if "params" in variables else variables
    put = (lambda a: jax.device_put(a, device)) if device is not None else (lambda a: a)
    n = sum(1 for k in p if k.startswith("layers_"))

    def layers():
        for i in range(n):
            blk = p[f"layers_{i}"]
            yield {
                "wq": put(blk["attn"]["q_proj"]["kernel"]),
                "wk": put(blk["attn"]["k_proj"]["kernel"]),
                "wv": put(blk["attn"]["v_proj"]["kernel"]),
                "wo": put(blk["attn"]["o_proj"]["kernel"]),
                "w_gate": put(blk["mlp"]["gate_proj"]["kernel"]),
                "w_up": put(blk["mlp"]["up_proj"]["kernel"]),
                "w_down": put(blk["mlp"]["down_proj"]["kernel"]),
                "attn_norm": put(blk["attn_norm"]["scale"]),
                "mlp_norm": put(blk["mlp_norm"]["scale"]),
            }

    return (
        put(p["tok_embed"]["embedding"]), layers(),
        put(p["final_norm"]["scale"]), put(p["lm_head"]["kernel"]),
    )
