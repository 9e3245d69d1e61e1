"""Paged decode attention's share of its roofline in a model whose layers
differ: as `paged_decode_roofline`, with the keys a layer attends and its
query heads taken from the configuration's pattern by its glue
(`window_decode_call`: a window layer reads min(keys, window) keys of a
row), over the device time under `args.scope` in the WHOLE runs of
`args.program` that pair with a step the runner kept
(`readers/latent_steps.py`). Such a model shares no block between rows (the
engine refuses a prefix cache beside window layers), so a step's distinct
keys are its rows' keys. Left out (never 0) where the glue has no such count
or there is nothing to read."""

from .. import modelglue
from . import latent_steps


def read(args, env):
    cfg = env.cell["config"]
    glue = modelglue.glue(cfg)
    if not hasattr(glue, "window_decode_call"):
        return None
    itemsize = modelglue.itemsize(cfg, "kv_cache")
    return latent_steps.read(
        args, env, latent_steps.decode_steps(env),
        lambda step: glue.window_decode_call(cfg, step["keys"], itemsize),
        "windowed decode attention")
