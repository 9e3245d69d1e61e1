"""The linear layers' chunk scan as a share of its roofline in the prefill
chunks of the traced slice: the least time the chip could take to do the
delta rule's FLOPs for each chunk's REAL tokens, at the precision the
configuration states for the recurrent state, and to read and write the
row's state block once a layer, as the configuration's glue counts them
(`chunk_scan_call`, from the tokens each `serve:prefill_chunk` annotation
says: the count is of the rule, whatever implements it), over the device time
of the operations under `args.scope` in the WHOLE runs of `args.program`
(every bucket's program has the name) that pair with an annotated chunk
(`readers/latent_steps.py`'s pairing of dispatch order with run order).
Compute-bound; a chunk's padding, the chunked form's own products (it does
more than the rule's FLOPs in fewer passes over the state) and everything
elementwise read as lost share. The scan is many operations a run and their
number differs between the buckets' programs, so a run is whole when it holds
as many of them as the most any run of ITS program holds. The key is left out
(never 0) where there is nothing to read: no trace, no annotations, no
`chunk_scan_call` in the glue, no such scope in the program (a program
without linear layers), no whole run that pairs with a chunk."""

from .. import modelglue
from . import latent_steps


def read(args, env):
    cfg = env.cell["config"]
    glue = modelglue.glue(cfg)
    if not hasattr(glue, "chunk_scan_call"):
        return None
    state = modelglue.itemsize(cfg, "recurrent_state")
    return latent_steps.read(
        args, env, latent_steps.annotations(env, args["annotation"]),
        lambda note: glue.chunk_scan_call(cfg, note["tokens"], state),
        "linear layers' chunk scan")
