"""The latent chunk attention kernel's share of its roofline in the prefill
chunks of the traced slice: the least time the chip could take to do the
absorbed scores and sums of all heads over the causal (query, key) pairs of
each chunk's REAL tokens and to read the keys' published latent rows once a
chunk, as the configuration's glue counts them (`latent_chunk_call`, from
the start and tokens each `serve:prefill_chunk` annotation says), over the
device time of the operations under `args.scope` in the WHOLE runs of
`args.program` (every bucket's program has the name) that pair with an
annotated chunk (`readers/latent_steps.py`). Compute-bound; a chunk's
padding and the pool's padding read as lost share. The key is left out
(never 0) where there is nothing to read, as in `latent_decode_roofline`."""

from .. import modelglue
from . import latent_steps


def read(args, env):
    glue = modelglue.glue(env.cell["config"])
    if not hasattr(glue, "latent_chunk_call"):
        return None
    return latent_steps.read(
        args, env, lambda cfg, note, itemsize: glue.latent_chunk_call(
            cfg, note["start"], note["tokens"], itemsize),
        "latent chunk kernel")
