"""The attention layers' cached attention as a share of its roofline in the
decode steps of the traced slice, for a model only SOME of whose layers
attend and whose configuration names no head size: the least time the chip
could take to read the K and V of the DISTINCT keys those steps attend in
the attention layers and to do every (row, key) pair's FLOPs, as the
configuration's glue counts them (`gqa_decode_call`, from what the runner
kept of every step it dispatched: each decoding row's keys, the step's
distinct keys), over the device time of the operations under `args.scope` in
the WHOLE runs of `args.program` that pair with a kept step
(`readers/latent_steps.py`): whatever implements the attention and in
whatever layout the pool holds a head. Memory-bound. The key is left out
(never 0) where there is nothing to read: no trace, no step kept, a glue
without the count, a program without the scope, no whole run."""

from .. import modelglue
from . import latent_steps


def read(args, env):
    cfg = env.cell["config"]
    glue = modelglue.glue(cfg)
    if not hasattr(glue, "gqa_decode_call"):
        return None
    itemsize = modelglue.itemsize(cfg, "kv_cache")

    def count(step):
        if step["distinct"] is None:
            return None
        return glue.gqa_decode_call(cfg, step["keys"], step["distinct"], itemsize)

    return latent_steps.read(args, env, latent_steps.decode_steps(env), count,
                             "grouped-query decode attention at head size 64")
