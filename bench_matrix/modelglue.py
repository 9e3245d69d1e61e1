"""What is the same for every model: the dtypes a configuration may name,
weights made on the device from the seed, and the way to a configuration's
glue. A glue module (`glue/<kind>.py`, named by the configuration's
`model.glue`) is the one place that knows both a family's configuration keys
and the program's parameter names; it gives

    build_model(config, max_seq_len, remat)  -> what ServeEngine and the trainers take
    reference_parts(variables, device=None)  -> (embedding, iterator of per-layer
        dicts, final norm, output matrix); a layer dict's keys are a matter
        between the glue and the `reference` module its configuration names
    train_flops_per_token(config, seq)       -> model FLOPs, for `readers/mfu.py`
        and, through `forward_flops` below, `readers/serve_mfu.py`
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def itemsize(config: dict, what: str) -> int:
    """Bytes a value of the configuration's `dtype[what]` takes."""
    return jnp.dtype(DTYPES[config["dtype"][what]]).itemsize


def glue(config: dict):
    """The module a configuration names under `model.glue`."""
    return importlib.import_module(config["model"]["glue"])


def build_model(config: dict, max_seq_len: int, remat: bool):
    """The program's model object at the configuration's sizes."""
    return glue(config).build_model(config, max_seq_len, remat)


def reference_parts(config: dict, variables, device=None):
    """The system's parameter tree, one layer at a time, in the names of the
    configuration's reference."""
    return glue(config).reference_parts(variables, device)


def train_flops_per_token(config: dict, seq: int) -> float:
    """Model FLOPs a trained token at this sequence length, as the
    configuration's kind counts them."""
    return glue(config).train_flops_per_token(config, seq)


def forward_flops(config: dict):
    """`f(start, tokens)`: model FLOPs of one forward pass over the `tokens`
    tokens at positions `start ...` of a sequence, each at its own context:
    the glue's count for a training token, forward third, is the MEAN over
    the positions of a `seq`-token sequence, so `seq` times it is the whole
    sequence's and the difference of two prefixes the tokens between them
    (a decoding token that attends k keys: `f(k - 1, 1)`). Active
    parameters only, attention by the keys attended, as the glue counts."""
    per_token = glue(config).train_flops_per_token
    prefix = {0: 0.0}

    def upto(n):
        if n not in prefix:
            prefix[n] = n * per_token(config, n) / 3.0
        return prefix[n]

    return lambda start, tokens: upto(start + tokens) - upto(start)


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
