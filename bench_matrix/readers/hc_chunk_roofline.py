"""The hyper-connection maps' share of their roofline in the prefill chunks
of the traced slice: the least time the chip could take to move the bytes
the maps' EQUATIONS need for each chunk's REAL tokens (the configuration's
glue counts them, `hc_call`, from the tokens each `serve:prefill_chunk`
annotation says: a sublayer reads the streams, writes the mixture, reads the
sublayer's output, reads the streams again and writes the new ones, whatever
implements that), over the device time of the operations under `args.scope`
in the WHOLE runs of `args.program` (every bucket's program has the name)
that pair with an annotated chunk (`readers/latent_steps.py`'s pairing of
dispatch order with run order). Memory-bound; a chunk's padding, a second
read of the streams inside one half of a map and the chain of small
reductions read as lost share. The maps are many operations a run and their
number differs between the buckets' programs, so a run is whole when it
holds as many of them as the most any run of ITS program holds. The key is
left out (never 0) where there is nothing to read: no trace, no annotations,
no `hc_call` in the glue, no such scope in the program (a program without
residual streams)."""

from .. import modelglue
from . import latent_steps


def read(args, env):
    cfg = env.cell["config"]
    glue = modelglue.glue(cfg)
    if not hasattr(glue, "hc_call"):
        return None
    itemsize = modelglue.itemsize(cfg, "activations")
    return latent_steps.read(
        args, env, latent_steps.annotations(env, args["annotation"]),
        lambda note: glue.hc_call(cfg, note["tokens"], itemsize),
        "hyper-connection maps")
