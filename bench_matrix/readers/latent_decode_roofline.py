"""The latent decode attention's share of its roofline in the decode steps
of the traced slice: the least time the chip could take to read the
published latent rows (576 values a key, whatever the pool holds beside
them) of the DISTINCT keys a step attends (a block that several rows' tables
hold is read once) and to do the absorbed scores and sums of all heads over
every (row, key) pair, as the configuration's glue counts them
(`latent_decode_call`, from what the runner kept of every step it
dispatched), over the device time of the operations under `args.scope` in
the WHOLE runs of `args.program` that pair with a kept step
(`readers/latent_steps.py`). At 242 FLOP/B a row's own keys sit on a v5e's
ridge and shared heads put the count on the compute side; the reader says
which side bounds it. The key is left out (never 0) where there is nothing
to read: no trace, no step kept, a glue without the count, a program
without the scope (the parent commit's), no whole run."""

from .. import modelglue
from . import latent_steps


def read(args, env):
    cfg = env.cell["config"]
    glue = modelglue.glue(cfg)
    if not hasattr(glue, "latent_decode_call"):
        return None
    itemsize = modelglue.itemsize(cfg, "kv_cache")
    return latent_steps.read(
        args, env, latent_steps.decode_steps(env),
        lambda step: None if step["distinct"] is None else glue.latent_decode_call(
            cfg, sum(step["keys"]), itemsize, distinct=step["distinct"]),
        "latent decode attention", calls=cfg["num_hidden_layers"])
