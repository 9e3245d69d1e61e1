"""The latent decode attention kernel's share of its roofline in the decode
steps of the traced slice: the least time the chip could take to read the
published latent rows (576 values a key, whatever the pool holds beside
them) that the decoding rows attend and to do the absorbed scores and sums
of all heads over them, as the configuration's glue counts them
(`latent_decode_call`, from the keys each `serve:decode_step` annotation
says), over the device time of the operations under `args.scope` in the
WHOLE runs of `args.program` that pair with an annotated step
(`readers/latent_steps.py`). At 242 FLOP/B the count sits on a v5e's ridge;
the reader says which side bounds it. The key is left out (never 0) where
there is nothing to read: no trace, no annotation, a glue without the count,
a program without the scope (the parent commit's), no whole run."""

from .. import modelglue
from . import latent_steps


def read(args, env):
    glue = modelglue.glue(env.cell["config"])
    if not hasattr(glue, "latent_decode_call"):
        return None
    return latent_steps.read(
        args, env, lambda cfg, note, itemsize: glue.latent_decode_call(
            cfg, note["keys"], itemsize),
        "latent decode kernel")
