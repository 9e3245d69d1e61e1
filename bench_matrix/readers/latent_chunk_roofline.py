"""The latent chunk attention's share of its roofline in the prefill chunks
of the traced slice: the least time the chip could take to do the scores and
sums of all heads over the causal (query, key) pairs of each chunk's REAL
tokens, in whichever of the two forms needs fewer operations at the chunk's
own size (absorbed, or the keys' heads up-projected once a chunk), and to
read the keys' published latent rows once a chunk, as the configuration's
glue counts them (`latent_chunk_call`, from the start and tokens each
`serve:prefill_chunk` annotation says), over the device time of the
operations under `args.scope` in the WHOLE runs of `args.program` (every
bucket's program has the name) that pair with an annotated chunk
(`readers/latent_steps.py`). Compute-bound; a chunk's padding and the pool's
padding read as lost share. The key is left out (never 0) where there is
nothing to read, as in `latent_decode_roofline`."""

from .. import modelglue
from . import latent_steps


def read(args, env):
    cfg = env.cell["config"]
    glue = modelglue.glue(cfg)
    if not hasattr(glue, "latent_chunk_call"):
        return None
    itemsize = modelglue.itemsize(cfg, "kv_cache")
    return latent_steps.read(
        args, env, latent_steps.annotations(env, args["annotation"]),
        lambda note: glue.latent_chunk_call(cfg, note["start"], note["tokens"], itemsize),
        "latent chunk attention", calls=cfg["num_hidden_layers"])
